"""Continuous-batching inference engine.

Reference: the reference serves LLMs by wrapping vLLM
(python/ray/llm/_internal/serve/engines/vllm/vllm_engine.py —
continuous batching, paged KV). TPU-native redesign (JetStream-style):

- The serving cache is a few static-shape arrays in HBM, each with the
  slot on axis 1: XLA-friendly, no paging indirection — slot b of the
  batch dimension is the "page table", assigned to one request at a
  time. What the arrays are is the model family's business
  (models/family.py): for the Llama family one pair of keys and values
  [L, B, S, KVH, HD]; for the Jamba family such a pair for its few
  attention layers beside the recurrent state of its Mamba layers
  ([M, B, N, d_inner] float32 and the convolution's last inputs). The
  engine holds the family's pytree as the list of its leaves, hands a
  slot over by writing a batch-1 entry over every leaf (donated, in
  place), and never looks inside.
- A family whose cache holds recurrent state runs the dense path only
  (admission, bucketed prefill told the prompt's true length, the
  whole-batch decode step, which moves every slot's state, a parked
  slot's too: that is junk the next admission replaces whole). Prefix
  caching, chunked prefill, speculative and multi-step decoding,
  LoRA banks and disaggregated prefill assume rows that can be written
  again; the engine refuses them for such a family, by name, instead of
  corrupting a state. They are the Llama family's programs over a pair
  of keys and values, so a family whose cache is rows of a latent
  (models/mla.py) is refused them too, for that reason
  (``ModelFamily.dense_only``).
- Decode is a single jitted step for the WHOLE batch every iteration;
  requests join (prefill into a free slot) and leave (EOS/length)
  between steps without recompiling — that is the continuous batching.
- The dense step is launched one ahead of its read-back: step N+1 goes
  out from the state and the cache that step N returns, and the host
  reads, emits and launches beside the device (``_fly``; the comment
  above ``_needs_order`` has what makes that safe and what keeps the
  old order).
- Prefill pads prompts into power-of-two buckets so only O(log S)
  prefill programs ever compile.

Sampling (temperature / top-k / greedy) is ON-DEVICE, fused into the
jitted decode step: only the sampled [B] int32 tokens cross to the
host each iteration, not [B, V] float logits (at 32k vocab x batch 8
that copy would eat the decode budget). Per-request temperature/top-k
ride in as [B] arrays; randomness is a counter-folded PRNG key so the
program never recompiles.

Multi-LoRA multiplexing (reference: vLLM multi-LoRA behind
serve.llm): adapters register into a fixed-size bank ({A,B} stacks,
index 0 = all-zero base); each request may name an adapter, and the
batched decode gathers per-slot A/B — different requests in the SAME
decode batch can use different adapters.
"""

from __future__ import annotations

import collections
import functools
import itertools
import threading
import weakref

from ray_tpu.devtools import locktrace
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from ray_tpu.accelerators import jax_backend
from ray_tpu.models.family import family_of, insert_slot
from ray_tpu.models.llama import (
    LlamaConfig, llama_decode_step, llama_prefill, llama_verify_step)
from ray_tpu.ops import attention as _attention_op
from ray_tpu.ops import selective_scan as _scan_op
from ray_tpu.util import flight_recorder as _flight
from ray_tpu.util import metrics as _metrics

# --- built-in engine metrics (reference: vLLM engine stats surfaced
# through serve) ----------------------------------------------------
# Everything the stepper thread records, and what a request thread
# records before the first token, aggregates locally in _MetricsBuffer
# and is flushed by the buffer's own thread: an RPC from the stepper of
# a replica worker would serialize the decode loop on the control
# plane, with every active slot waiting.
_TTFT_BOUNDS = [0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                10.0, 30.0, 60.0]
_STEP_BOUNDS = [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                0.5, 1.0, 5.0]
ENGINE_TTFT = _metrics.Histogram(
    "ray_tpu_engine_ttft_seconds",
    "Time from request admission to its first emitted token",
    boundaries=_TTFT_BOUNDS)
ENGINE_STAGE_SECONDS = _metrics.Histogram(
    "ray_tpu_engine_request_stage_seconds",
    "Where a request waits before its first token: dispatch (proxy "
    "receipt to the replica's handler), prepare (handler entry to "
    "add_request), queue (add_request to admission), prefill "
    "(admission to the first token)",
    boundaries=_TTFT_BOUNDS, tag_keys=("stage",))
ENGINE_STEP_SECONDS = _metrics.Histogram(
    "ray_tpu_engine_step_seconds",
    "Engine step wall time, by phase (prefill-admitting vs pure decode)",
    boundaries=_STEP_BOUNDS, tag_keys=("phase",))
ENGINE_STEP_HOST_SECONDS = _metrics.Histogram(
    "ray_tpu_engine_step_host_seconds",
    "Engine step wall time less the time the stepper waited for the "
    "device in it (its blocking read-backs and its hold before a step "
    "launched ahead): the host's own work in a step, by phase",
    boundaries=_STEP_BOUNDS, tag_keys=("phase",))
ENGINE_STEP_UPLOAD_SECONDS = _metrics.Histogram(
    "ray_tpu_engine_step_upload_seconds",
    "Time the stepper spent sending a step's inputs to the device "
    "(0.0 for a step that sent nothing), by phase",
    boundaries=_STEP_BOUNDS, tag_keys=("phase",))
ENGINE_TOKENS = _metrics.Counter(
    "ray_tpu_engine_tokens_generated_total",
    "Tokens emitted by the engine")
ENGINE_STATE_UPLOADS = _metrics.Counter(
    "ray_tpu_engine_state_uploads_total",
    "Dense decode steps that sent the per-slot state from the host: "
    "the others took it from the decode step before them")
ENGINE_DECODE_LAUNCHES = _metrics.Counter(
    "ray_tpu_engine_decode_launches_total",
    "Dense decode programs launched, by order: ahead (the decode step "
    "before it was still unread: the host's work on that one runs beside "
    "this one) or in_order (the host had read everything: an idle "
    "engine's first step, a step behind an admission, today's order)",
    tag_keys=("order",))
ENGINE_DISCARDED_TOKENS = _metrics.Counter(
    "ray_tpu_engine_discarded_tokens_total",
    "Tokens the device sampled for a live row that no request took: "
    "the request had ended (a stop id, a cancel) when the token was "
    "read; a step launched ahead costs one such token per ending the "
    "host learns from a token")
ENGINE_SAMPLER_STEPS = _metrics.Counter(
    "ray_tpu_engine_sampler_steps_total",
    "Decode programs launched, by the sampler's branch their live slots "
    "engage: topk (a slot samples among its top k), full (a slot samples "
    "over the whole vocabulary; a step may engage both) or greedy "
    "(neither: every live slot takes the arg-max)",
    tag_keys=("path",))
ENGINE_PREFILL_TOKENS = _metrics.Counter(
    "ray_tpu_engine_prefill_tokens_total",
    "Positions the prefill programs computed, by kind: real (a "
    "prompt's own tokens) or pad (what its bucket added)",
    tag_keys=("kind",))
ENGINE_DECODE_KV_ROWS = _metrics.Counter(
    "ray_tpu_engine_decode_kv_rows_total",
    "Rows of one layer's KV cache a dense decode step's attention "
    "covered (read: the blocks up to each live slot's position and a "
    "parked slot's park row) and the rest of its slots x max_seq rows "
    "(skipped); all read where the decode kernel does not engage",
    tag_keys=("kind",))
ENGINE_STATE_SLOTS = _metrics.Counter(
    "ray_tpu_engine_state_slots_total",
    "Slots x recurrent layers of the dense decode steps, by what the "
    "step did with the slot's recurrent state: moved (a live slot's, "
    "one step on) or parked (an empty slot's, neither read nor "
    "written); only from a family whose decode step skips parked state",
    tag_keys=("kind",))
ENGINE_EXPERT_PICKS = _metrics.Counter(
    "ray_tpu_engine_expert_picks_total",
    "Experts picked by the router for live rows (a prompt's own "
    "positions, a live slot's decode step; parked slots and padding "
    "not counted), by where the expert lives: held (this device "
    "computed it) or absent (another rank of its expert-parallel group "
    "does)",
    tag_keys=("where",))
ENGINE_EXPERT_SLOTS = _metrics.Counter(
    "ray_tpu_engine_expert_slots_total",
    "Held experts over the layers of the dense decode steps, by state: "
    "hit (at least one live row picked it) or idle (none did, its "
    "weights were read for nothing)",
    tag_keys=("state",))
ENGINE_EXPERT_PAIRS_WALKED = _metrics.Counter(
    "ray_tpu_engine_expert_pairs_walked_total",
    "Places of the sorted (row, pick) order that the prefills' expert "
    "layers gathered and multiplied, padding's rows too (every place "
    "where the rank holds half the experts or more, the walk's whole "
    "chunks below that): over top_k x "
    "ray_tpu_engine_prefill_tokens_total (both kinds) and the routed "
    "layers, the share of all pairs that were walked; a decode step's "
    "form walks none")
ENGINE_ROUTER_PICKS = _metrics.Counter(
    "ray_tpu_engine_router_picks_total",
    "Experts picked for live rows by a router that adds a selection "
    "bias to its scores for the choice alone, by what the bias did: "
    "moved (the pick is not among the row's k largest scores) or kept; "
    "only from a family whose router has such a bias",
    tag_keys=("bias",))
# the expert layers' device counts (parallel.moe.EXPERT_COUNTS and
# BIAS_COUNTS) that are series: name -> (counter, tags)
_EXPERT_COUNT_SERIES = {
    "picks_held": (ENGINE_EXPERT_PICKS, {"where": "held"}),
    "picks_absent": (ENGINE_EXPERT_PICKS, {"where": "absent"}),
    "slots_hit": (ENGINE_EXPERT_SLOTS, {"state": "hit"}),
    "slots_idle": (ENGINE_EXPERT_SLOTS, {"state": "idle"}),
    "pairs_walked": (ENGINE_EXPERT_PAIRS_WALKED, None),
    "picks_bias_moved": (ENGINE_ROUTER_PICKS, {"bias": "moved"}),
    "picks_bias_kept": (ENGINE_ROUTER_PICKS, {"bias": "kept"})}
ENGINE_ADMIT_LAUNCH_SECONDS = _metrics.Histogram(
    "ray_tpu_engine_admit_launch_seconds",
    "Time from the stepper's pop of a waiting request to the return of "
    "its prefill program's dispatch: how long the host kept the device "
    "from this prompt's prefill. overlapped=1 where the prompt admitted "
    "before it was still being prefilled, so the time cost the device "
    "nothing",
    boundaries=_STEP_BOUNDS, tag_keys=("overlapped",))
ENGINE_STEPPER_SECONDS = _metrics.Counter(
    "ray_tpu_engine_stepper_seconds_total",
    "Wall seconds of the stepper thread's life by what it was in: the "
    "phases exclude each other and sum to the time its loop has run",
    tag_keys=("phase",))
ENGINE_STEPPER_CPU_SECONDS = _metrics.Counter(
    "ray_tpu_engine_stepper_cpu_seconds_total",
    "Seconds the stepper thread spent on a CPU, in the stretches in "
    "which it read that clock, by phase, python standing for the five "
    "of plain Python (admit, bias, gather, emit, other)",
    tag_keys=("phase",))
ENGINE_STEPPER_CPU_WALL_SECONDS = _metrics.Counter(
    "ray_tpu_engine_stepper_cpu_wall_seconds_total",
    "Wall seconds of those stretches, by the same phases: less the CPU "
    "seconds, what the thread waited there; in python, for the "
    "interpreter or a CPU",
    tag_keys=("phase",))
ENGINE_CACHE_BYTES = _metrics.Gauge(
    "ray_tpu_engine_cache_bytes",
    "Bytes of the serving cache, by kind: kv (rows of keys and values), "
    "recurrent (state a decode step consumes and replaces) or latent "
    "(rows that an attention layer reads as keys and as values)",
    tag_keys=("kind",))
ENGINE_TOKENS_PER_S = _metrics.Gauge(
    "ray_tpu_engine_tokens_per_second",
    "Decode throughput over the last metrics flush window")
ENGINE_OCCUPANCY = _metrics.Gauge(
    "ray_tpu_engine_batch_occupancy",
    "Active decode slots (continuous-batching occupancy)")
ENGINE_WAITING = _metrics.Gauge(
    "ray_tpu_engine_waiting_requests",
    "Requests queued for a free decode slot")


# The stepper's phases and the span that means each: what has no span
# of its own (has_work, the step() wrapper, building ``active``) is
# ``other``, engine.step's own phase. The thread's CPU clock is a
# system call (6-25 us, in ticks of 10 ms, on the benchmark's machine):
# it is read only where the stepper enters or leaves a phase in which
# it may sleep by intent (the five of plain Python share ``python``),
# and only in one stretch in four between two waits for the device.
STEPPER_PHASES = ("wait", "admit", "bias", "gather", "upload", "launch",
                  "blocked", "emit", "other")
STEPPER_CPU_PHASES = ("wait", "python", "upload", "launch", "blocked")
_CPU_PHASE = (0, 1, 1, 1, 2, 3, 4, 1, 1)
# a list of the account, its counter and the phases that index both
_STEPPER_FAMILIES = (
    ("wall", ENGINE_STEPPER_SECONDS, STEPPER_PHASES),
    ("cpu", ENGINE_STEPPER_CPU_SECONDS, STEPPER_CPU_PHASES),
    ("cpu_wall", ENGINE_STEPPER_CPU_WALL_SECONDS, STEPPER_CPU_PHASES))
_WAIT, _ADMIT, _BIAS, _GATHER, _UPLOAD, _LAUNCH, _BLOCKED, _EMIT, _OTHER = \
    range(len(STEPPER_PHASES))
_SPAN_PHASE = {
    "engine.wait": _WAIT, "engine.prefill": _ADMIT, "engine.bias": _BIAS,
    "engine.gather": _GATHER, "engine.upload": _UPLOAD,
    "engine.launch": _LAUNCH, "engine.insert": _LAUNCH,
    "engine.readback": _BLOCKED, "engine.hold": _BLOCKED,
    "engine.emit": _EMIT,
    "engine.step": _OTHER}


class _StepperAccount:
    """Where the stepper thread's time went. ``wall``: seconds by phase,
    exclusive, so they sum to the time since ``bind`` first ran,
    exactly. ``cpu``: the thread's CPU seconds by CPU phase over the
    stretches in which that clock is read, and ``cpu_wall`` the wall
    seconds of the same stretches. Only the stepper writes (a switch
    charges what passed to the phase it leaves); the metrics buffer's
    flush copies the lists and the process's stall watch reads
    ``probe``."""

    __slots__ = ("clock", "cpu_clock", "cpu_every", "wall", "cpu",
                 "cpu_wall", "phase", "thread", "prefills", "t", "_c",
                 "_tc", "_reading", "_blocks")

    def __init__(self):
        self.clock, self.cpu_clock = time.perf_counter, time.thread_time
        self.cpu_every = 4
        self.wall = [0.0] * len(STEPPER_PHASES)
        self.cpu = [0.0] * len(STEPPER_CPU_PHASES)
        self.cpu_wall = [0.0] * len(STEPPER_CPU_PHASES)
        self.phase = _OTHER
        self.thread: Optional[int] = None
        self.prefills = 0   # engine.prefill spans open: two may overlap
        self.t = 0.0        # ``clock`` at the last switch
        self._c = self._tc = 0.0   # both clocks at the last CPU reading
        self._reading, self._blocks = False, 0

    def bind(self) -> None:
        """The caller is the stepper from here on: its loop's first
        turn starts the clock. A thread that takes the loop over reads
        a CPU clock of its own."""
        ident = threading.get_ident()
        if ident != self.thread:
            if self.thread is None:
                self.t = self.clock()
            self.thread, self._reading = ident, False

    def probe(self) -> Optional[tuple]:
        """For the stall watch, on its thread: the phase the stepper is
        in and the clock at its last switch, None while it waits for
        work. Two loads; the stepper writes nothing for it."""
        phase = self.phase
        return None if phase == _WAIT else (STEPPER_PHASES[phase], self.t)

    def switch(self, phase: int) -> None:
        t, before = self.clock(), self.phase
        self.wall[before] += t - self.t
        self.t, self.phase = t, phase
        kind = _CPU_PHASE[before]
        if kind != _CPU_PHASE[phase]:
            if self._reading:
                c = self.cpu_clock()
                self.cpu[kind] += c - self._c
                self.cpu_wall[kind] += t - self._tc
                self._c, self._tc = c, t
            if phase == _BLOCKED:
                # the device has work, so a reading here delays nothing:
                # a stretch of readings ends, every fourth time one starts
                self._blocks += 1
                self._reading = not self._blocks % self.cpu_every
                if self._reading:
                    self._c, self._tc = self.cpu_clock(), t


class _StepperSpan(_flight.span):
    """An engine span opened by the stepper: entering sets its phase,
    leaving restores the one before. engine.prefill spans are opened
    under engine.step only and overlap since PR 40, so leaving one
    restores ``admit`` while another is open and ``other`` after."""

    __slots__ = ("_account", "_phase", "_before")

    def __init__(self, account: _StepperAccount, name: str, **args):
        super().__init__("serve", name, **args)
        self._account, self._phase = account, _SPAN_PHASE[name]

    def __enter__(self) -> "_StepperSpan":
        account = self._account
        self._before = account.phase
        account.prefills += self._phase == _ADMIT
        account.switch(self._phase)
        return super().__enter__()

    def __exit__(self, *exc_info) -> None:
        super().__exit__(*exc_info)
        account, before = self._account, self._before
        if self._phase == _ADMIT:
            account.prefills -= 1
            before = _ADMIT if account.prefills else _OTHER
        account.switch(before)


class _MetricsBuffer(_metrics.LocalBuffer):
    """The engine's metrics, aggregated in its process: histograms as
    bucket counts, so a flush is one metrics.record_batch (one
    control-plane RPC from a worker) of a size that does not grow with
    the step rate. No caller on the stepper thread ever flushes: a
    daemon thread owned by the buffer does, every ``flush_interval_s``,
    and stats() / flush_metrics() do on their caller's thread. The
    thread starts with the first recorded step, knows the engine only
    by a weak reference and ends when the engine is collected or
    close() runs."""

    def __init__(self, engine, flush_interval_s: float = 0.5):
        super().__init__()
        self.flush_interval_s = flush_interval_s
        self._engine = weakref.ref(engine)
        self._last_flush = time.perf_counter()
        self._flushed_tokens = 0
        # the stepper's account, list by list, as the last flush read it
        self.stepper_seconds = [[0.0] * len(phases)
                                for _, _, phases in _STEPPER_FAMILIES]
        # the expert layers' device counts as the last flush read them
        # (uint32 on the device: a difference is taken modulo 2**32),
        # and their sums since the engine began, by name
        self.expert_read = [0] * len(engine._family.expert_counts)
        self.expert_totals = dict.fromkeys(engine._family.expert_counts, 0)
        # flushers: the buffer's thread and stats()/flush_metrics()
        # callers on request threads; never the stepper
        self._flush_lock = locktrace.traced_lock("llm.engine.flush")
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # the stall watch's counts ride this buffer's flushes
        _flight.stall_carriers.add(self)

    def note_step(self, phase: str, dt: float, host_dt: float,
                  upload_dt: float, tokens: int) -> None:
        tags = {"phase": phase}
        self.observe(ENGINE_STEP_SECONDS, dt, tags)
        self.observe(ENGINE_STEP_HOST_SECONDS, host_dt, tags)
        self.observe(ENGINE_STEP_UPLOAD_SECONDS, upload_dt, tags)
        if tokens:
            self.inc(ENGINE_TOKENS, float(tokens))
        if self._thread is None and not self._stop.is_set():
            # only the stepper gets here, so one thread per engine
            self._thread = threading.Thread(
                target=self._flush_loop, name="engine-metrics-flush",
                daemon=True)
            self._thread.start()

    def _flush_loop(self) -> None:
        while not self._stop.wait(self.flush_interval_s):
            if self._engine() is None:
                return
            self.flush()

    def flush(self, force: bool = False) -> List[tuple]:
        """Ship what gathered since the last flush plus the gauges as
        they stand; nothing (and no RPC) when nothing gathered, unless
        forced."""
        engine = self._engine()
        with self._flush_lock:
            now = time.perf_counter()
            stalls = _flight.take_stall_counts()
            if stalls:
                self.merge(stalls)
            if engine is not None and (force or self._pending.histograms
                                       or self._pending.counters):
                elapsed = now - self._last_flush
                self._last_flush = now
                total = engine.total_generated
                if elapsed > 0:
                    self.set(ENGINE_TOKENS_PER_S,
                             (total - self._flushed_tokens) / elapsed)
                self._flushed_tokens = total
                self.set(ENGINE_OCCUPANCY, float(sum(
                    1 for s in engine.slots if s.request is not None)))
                self.set(ENGINE_WAITING, float(len(engine.waiting)))
                # the stepper never brings its account: its growth
                # since the last flush is read here (a copy of a list
                # is one step of the interpreter)
                for (kind, counter, phases), sent in zip(
                        _STEPPER_FAMILIES, self.stepper_seconds):
                    read = list(getattr(engine._account, kind))
                    for phase, now_s, sent_s in zip(phases, read, sent):
                        self.inc(counter, now_s - sent_s, {"phase": phase})
                    sent[:] = read
                self._read_expert_counts(engine)
            try:
                return super().flush()
            except Exception:  # graftlint: disable=GL004
                return []  # observability is best-effort

    def _read_expert_counts(self, engine) -> None:
        """What the family's expert layers counted on the device since
        the last flush. The array is the one the newest program
        returned: reading it waits for that program, here on the
        flusher's thread, never on the stepper's."""
        names = engine._family.expert_counts
        if not names:
            return
        read = [int(v) for v in np.asarray(engine._expert_counts)]
        for name, now, sent in zip(names, read, self.expert_read):
            grown = (now - sent) % 2**32
            self.expert_totals[name] += grown
            if grown and name in _EXPERT_COUNT_SERIES:
                counter, tags = _EXPERT_COUNT_SERIES[name]
                self.inc(counter, float(grown), tags)
        self.expert_read = read

    def close(self) -> None:
        """Stop the flush thread after one last flush."""
        _flight.stall_carriers.discard(self)
        self._stop.set()
        thread = self._thread
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=2.0)
        self.flush()


class EngineSaturatedError(RuntimeError):
    """Raised by add_request when the waiting queue is at
    EngineConfig.max_waiting_requests — the reject-before-enqueue
    hook serve admission control builds on (the LLM server converts
    this into a typed BackpressureError / HTTP 503)."""

    def __init__(self, waiting: int, cap: int):
        self.waiting = waiting
        self.cap = cap
        super().__init__(
            f"engine waiting queue is full ({waiting}/{cap}); "
            "retry after the batch drains")


@dataclass
class EngineConfig:
    # default vocab covers the ByteTokenizer's 258 ids (256 bytes + BOS/EOS).
    # A LlamaConfig or a JambaConfig: the engine asks the configuration's
    # family (models/family.py) for the model's functions.
    model: Any = field(
        default_factory=lambda: LlamaConfig.tiny(vocab_size=258))
    max_batch: int = 8
    max_seq: int = 512
    tokenizer: Optional[str] = None  # None/"byte" or an HF id
    seed: int = 0
    # multi-LoRA bank size (adapter slot 0 is the zero/base adapter);
    # 0 disables the LoRA path entirely (no bank in the decode program)
    max_loras: int = 0
    lora_rank: int = 8
    # Weight-only quantization for serving (reference: vLLM
    # quantization passthrough, vllm_models.py:214). "int8" quantizes
    # the target model's FFN stacks on load (per-output-channel
    # scales; Pallas in-register-dequant matmul on TPU — see
    # ops/quant_matmul.py). None serves in the working dtype.
    quantization: Optional[str] = None
    # Static top-k width for on-device sampling: XLA needs a fixed
    # lax.top_k width, so per-request top_k is CLAMPED to this (also at
    # add_request, so the effective value is visible on the request).
    # top_k=0 samples the full vocab.
    max_top_k: int = 256
    # Speculative decoding (reference: vLLM spec-decode): a small
    # draft model greedily proposes spec_tokens-1 tokens per round and
    # the target scores the whole chunk in ONE llama_verify_step
    # forward — up to spec_tokens tokens emitted per target forward.
    # Greedy (temperature<=0) requests get the speculative fast path;
    # sampled requests fall back to one target-verified token per
    # round (still correct, no speedup). None disables.
    # Numerics: every emitted token is the argmax of TARGET logits
    # computed by the chunked verify program; in bf16 that can break
    # argmax ties differently than the single-token decode program
    # (bitwise parity with the dense path holds in f32).
    draft_model: Optional[LlamaConfig] = None
    spec_tokens: int = 4
    # Multi-step scheduling (reference: vLLM --num-scheduler-steps):
    # fuse multi_step decode iterations into ONE device dispatch
    # (lax.scan), amortizing host-device round trips when decode is
    # dispatch-bound. Tokens a request cannot absorb (stop token or
    # max_tokens hit mid-chunk) are discarded host-side: greedy
    # outputs are identical to single-step decoding; sampled requests
    # draw from the same distributions under a different RNG stream.
    # Mutually exclusive with draft_model (the draft cache cannot be
    # kept in sync through a fused chunk).
    multi_step: int = 1
    # Automatic prefix caching (reference: vLLM
    # --enable-prefix-caching): completed prompt KV blocks are kept in
    # an LRU keyed by the token prefix; a new prompt sharing a cached
    # prefix prefills ONLY its suffix (one llama_verify_step chunk at
    # the prefix boundary). Pays off when requests share a long
    # system prompt. Entries hold device (HBM) KV blocks — size the
    # LRU to the memory you can spare. LoRA prefills bypass the cache
    # (adapter-specific KV must not leak across adapters).
    enable_prefix_caching: bool = False
    prefix_cache_entries: int = 16
    prefix_cache_min_tokens: int = 8
    # Chunked prefill (reference: vLLM --enable-chunked-prefill):
    # instead of admission running one whole-prompt prefill that
    # stalls every decoding request for the prompt's full forward,
    # prompts prefill in chunks of this many tokens, one chunk per
    # step, interleaved with decode dispatches — bounding the
    # inter-token latency hit of a long prompt joining the batch to
    # ~one chunk forward. 0 disables. Mutually exclusive with
    # draft_model and enable_prefix_caching; LoRA-adapter requests
    # fall back to blocking prefill.
    chunked_prefill_tokens: int = 0
    # Reject-before-enqueue backpressure (serve admission control):
    # add_request raises EngineSaturatedError instead of appending
    # once this many requests are already waiting — bounding the
    # engine queue so the serve chain sheds instead of building an
    # invisible in-engine backlog. 0 disables (unbounded waiting).
    max_waiting_requests: int = 0


@dataclass
class GenerationRequest:
    prompt_ids: List[int]
    max_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    stop_ids: tuple = ()
    # OpenAI-style logit bias: {token_id: bias} added to the target
    # logits before sampling, every step (values clamped to +-100).
    # Applied in ALL decode paths; speculative drafts propose without
    # it, so a bias that changes the argmax lowers draft acceptance
    # but never affects outputs.
    logit_bias: Optional[Dict[int, float]] = None
    # OpenAI presence/frequency penalties: subtracted from the logits
    # of already-generated tokens each step (presence once per distinct
    # token, frequency per occurrence). Implemented on the SAME
    # device-bias-row machinery as guided decoding: the row is
    # recomputed host-side after each emission (bias_stale) — one [V]
    # upload per penalized slot per step.
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # OpenAI logprobs: None = off; an int >= 0 = number of top
    # alternatives to record per emitted token (0 still records the
    # CHOSEN token's logprob with an empty top list, matching OpenAI's
    # logprobs=0 / top_logprobs=0 semantics; clamped to the engine's
    # static top-k width). Logprobs are log-softmax of the BIASED but
    # UN-temperature-scaled logits — the model's distribution after
    # logit_bias/penalties/grammar masks, before sampling temperature
    # and top-k truncation (the raw-logprobs convention; a sampled
    # token's reported logprob is not its realized sampling
    # probability at temperature != 1). Requests with logprobs take
    # the dense decode path (the fused multi-token paths do not
    # return per-step logprob tensors).
    logprobs: Optional[int] = None
    # Guided decoding (reference: vLLM guided decoding behind
    # response_format/tools): a ray_tpu.llm.guided.TokenConstraint.
    # Its per-state token mask folds into the slot's device bias row
    # (-1e9 on disallowed ids) so the constraint is enforced inside
    # the on-device sampler; the engine advances guided_state per
    # emitted token. Fast batch paths that cannot refresh masks
    # mid-chunk (speculative, multi-step) fall back to dense stepping
    # while any guided request is active.
    guided: Optional[Any] = None
    guided_state: Any = None
    # LoRA adapter name (must be register_adapter'd); None = base model
    adapter: Optional[str] = None
    request_id: int = field(default_factory=itertools.count().__next__)
    # Streaming: when set (queue.Queue), the stepper pushes each emitted
    # token as it decodes; None terminates the stream (reference: vLLM's
    # per-request output stream consumed by serve token streaming).
    stream_queue: Optional[Any] = None
    # filled by the engine
    output_ids: List[int] = field(default_factory=list)
    # per emitted token (when logprobs > 0):
    # {"id", "logprob", "top": [(id, logprob), ...]}
    logprob_data: List[Dict[str, Any]] = field(default_factory=list)
    finish_reason: Optional[str] = None
    error: Optional[str] = None
    # time.perf_counter() at add_request, at the moment the stepper
    # popped the request from the waiting queue, and at its first
    # token: queue = t_admit - t_submit, prefill = t_first_token -
    # t_admit, and their sum is the engine's TTFT
    t_submit: Optional[float] = field(default=None, repr=False,
                                      compare=False)
    t_admit: Optional[float] = field(default=None, repr=False,
                                     compare=False)
    t_first_token: Optional[float] = field(default=None, repr=False,
                                           compare=False)
    # set when finish_reason lands; waiters block on this instead of
    # polling `done` in a sleep loop (graftlint GL003)
    done_event: threading.Event = field(default_factory=threading.Event,
                                        repr=False, compare=False)

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    def finish(self, reason: str, error: Optional[str] = None) -> None:
        """Mark finished and wake waiters. The ONE completion path —
        assigning finish_reason directly would leave done_event unset
        and strand ``wait_done`` callers."""
        if error is not None:
            self.error = error
        self.finish_reason = reason
        self.done_event.set()

    def wait_done(self, timeout: Optional[float] = None) -> bool:
        """Block until the engine finishes this request. Returns
        ``done`` (False on timeout)."""
        self.done_event.wait(timeout)
        return self.done

    def push_stream(self, item) -> None:
        if self.stream_queue is not None:
            try:
                self.stream_queue.put_nowait(item)
            except Exception:  # graftlint: disable=GL004
                pass  # stream consumer is gone; tokens just drop


# Rows of the dense decode step's state, one int32 [7, B] array that
# lives on the device (temperature rides along bit-cast). A step changes
# TOKEN, POS and STEP (the sampler's counter, the same in every column);
# the rest only admission and endings do.
_TOKEN, _POS, _TEMP, _TOPK, _LORA, _LIVE, _STEP = range(7)


def _sample_tokens(logits, temp, topk, key, bias=None, live=None, *,
                   max_k: int):
    """On-device sampling: greedy / temperature / top-k per slot,
    [B, V] logits -> [B] int32 — only the token ids cross to the host.
    ``bias`` [B, V] is the per-slot logit_bias; ``live`` [B] marks the
    slots whose token is read (all of them where it is None).

    The arg-max is always computed; the rest runs only where a live
    slot asks for it: the sort and the draw among its ``max_k`` values
    if one samples with ``topk > 0``, the draw over the whole
    vocabulary if one samples with ``topk == 0``. A slot that samples
    draws what it would with every branch taken (same keys, same
    order); a slot that does not, or is not live, gets its arg-max."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("sampler"):
        n_b = logits.shape[0]
        if bias is not None:
            logits = logits + bias
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampling = temp > 0.0
        if live is not None:
            sampling &= live > 0
        among_top = topk > 0

        def unasked():
            return greedy

        def draw():
            scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
            keys = jax.random.split(key, n_b)

            def full():
                return jax.vmap(jax.random.categorical)(
                    keys, scaled).astype(jnp.int32)

            def top():
                vals, idx = jax.lax.top_k(scaled, max_k)
                mask = (jnp.arange(max_k)[None, :]
                        < jnp.clip(topk, 1, max_k)[:, None])
                vals = jnp.where(mask, vals, -jnp.inf)
                choice = jax.vmap(jax.random.categorical)(keys, vals)
                return jnp.take_along_axis(
                    idx, choice[:, None], axis=1)[:, 0].astype(jnp.int32)

            sampled = jnp.where(
                among_top,
                jax.lax.cond(jnp.any(sampling & among_top), top, unasked),
                jax.lax.cond(jnp.any(sampling & ~among_top), full,
                             unasked))
            return jnp.where(sampling, sampled, greedy)

        return jax.lax.cond(jnp.any(sampling), draw, unasked)


class _Slot:
    def __init__(self, index: int):
        self.index = index
        self.request: Optional[GenerationRequest] = None
        self.pos = 0            # position of the NEXT token to decode
        self.next_token = 0
        # False when the draft cache lacks this slot's prompt prefix
        # (disagg adopt without usable prompt_ids) — speculation is
        # skipped while such a slot is active
        self.draft_ready = True
        # chunked prefill: prompt tokens still being prefilled
        self.prefilling = False
        self.prefill_ids: Optional[List[int]] = None
        self.prefill_pos = 0
        # guided decoding: the slot's device bias row no longer matches
        # the request's automaton state (refreshed at the next step)
        self.bias_stale = False
        # logprob rows (chosen_lp, top_vals, top_ids) for the token
        # about to be emitted; consumed (and cleared) by _emit
        self.pending_lp = None


class _Launched(NamedTuple):
    """What ``_run_prefill`` left queued on the device for one prompt."""
    entry: list       # the prompt's cache entry, leaf by leaf
    bias: Any         # the request's [V] row (the shared zero row if none)
    sampled: tuple    # (token, chosen_lp, top_vals, top_ids) of
    #                   sample_one; without logprobs the last three None
    t_launched: float  # perf_counter() when the prefill's dispatch returned


class _Admission(NamedTuple):
    """A prompt in its slot with every program of its admission queued
    and its first token not read yet."""
    slot: _Slot
    sampled: tuple    # as in _Launched
    span: Any         # its engine.prefill span, open until the token is out


class _Flight(NamedTuple):
    """A dense decode step on the device's queue whose tokens the host
    has not read."""
    rows: tuple       # (slot, request) of every row launched live
    state: Any        # the state the program returned: row _TOKEN is read
    logprobs: Any     # (chosen, top_vals, top_ids) of a logprobs step
    t_launched: float  # the account's clock when the dispatch returned


# The hold before a step is launched ahead ends this long before the
# host's lead (its own recent launch time) before the expected end of
# the step that runs: room for the thread's wake. The step's length and
# the lead are observed, not set. (0.8 ms read the same gaps and a
# first token 1 ms later in the Mistral cell: PERF.md section 6, PR 67.)
_HOLD_SLACK_S = 0.0004
# a read-back that returned sooner found its step ended already
_READ_WAITED_S = 0.00005
_STEP_SAMPLES = 8     # decode steps whose shortest the hold reckons with


class ContinuousBatchingEngine:
    def __init__(self, config: EngineConfig, params=None,
                 draft_params=None):
        import jax
        import jax.numpy as jnp

        jax_backend.track_compile_time()
        self._jax = jax
        self._jnp = jnp
        self.config = config
        c = config.model
        fam = self._family = family_of(c)
        if fam.dense_only:
            self._refuse_beyond_dense(config, fam.dense_only)
        # name -> (jitted, abstract args, static kwargs) of the first
        # call of each hot program, and the Pallas kernels its lowered
        # text holds (filled by stats())
        self._program_sigs: Dict[str, tuple] = {}
        self._program_kernels: Dict[str, List[str]] = {}
        # Random weights (real checkpoints load via orbax/train), drawn
        # under jit: eagerly, llama_init holds each stacked weight in
        # float32 twice before casting, which took a 16-layer 7B-width
        # engine to 12.75 GiB of a v5e's 15.75 at construction.
        def init(key, model):
            return jax.jit(family_of(model).init, static_argnums=1)(
                key, model)

        if params is None:
            params = init(jax.random.PRNGKey(config.seed), c)
        if config.quantization is not None:
            if config.quantization != "int8":
                raise ValueError(
                    f"unknown quantization {config.quantization!r} "
                    "(supported: \"int8\")")
            from ray_tpu.models.llama import quantize_llama_ffn
            # pre-quantized checkpoints (w1_q8 already present) load
            # as-is; float checkpoints quantize on load
            if "w1_q8" not in params["layers"]:
                params = quantize_llama_ffn(params, c)
        self.params = params
        self._base_key = jax.random.PRNGKey(config.seed)
        # The dense step's state goes from one decode program to the
        # next, and a program's results are committed to their device
        # when any of its arguments is. A state sent from the host has
        # to reach the program as one it returned does (jit keys its
        # programs on that too, and would compile ``decode`` twice), so
        # everything a step sends is put committed, where the params
        # are, and the caches with it: ``insert`` sees them before and
        # after the first decode step.
        self._on_device = self._sharding_beside(params)
        # The serving cache: the family's pytree (every leaf with the
        # slot on axis 1), kept as the list of its leaves so that a
        # program's results go back into it by position. For the Llama
        # family that is [cache_k, cache_v].
        cache_shape = jax.eval_shape(
            lambda: fam.init_cache(c, config.max_batch, config.max_seq))
        self._cache_def = cache_def = jax.tree.structure(cache_shape)
        self.cache_bytes = fam.cache_bytes(cache_shape)
        self.cache = self._fresh_cache(c)
        # per-slot logit_bias rows, device-resident so the per-step
        # cost is one [B, V] add — rows are (re)set at admission, so
        # stale rows from finished requests are never read
        self._bias, self._zero_bias_row = self._fresh_bias()

        def set_bias_row(bias, row, idx):
            return jax.lax.dynamic_update_slice(
                bias, row[None, :], (idx, 0))

        # idx stays a traced operand: dynamic_update_slice takes
        # dynamic starts, so ONE compile covers every slot (a static
        # idx would compile per slot index)
        self._set_bias = jax.jit(set_bias_row, donate_argnums=(0,))
        # Scratch region: every batched dispatch writes K/V rows for
        # ALL slots, so slots not participating park their writes in
        # the cache tail. Those rows must never hold live history —
        # rows BELOW a slot's position are attended without being
        # rewritten, so clobbering one corrupts generation (rows at or
        # above the position are always written before they become
        # visible). The region is sized for the widest parked write
        # (spec chunk, prefill chunk, multi-step burst, or the 1-row
        # dense step) and requests retire before reaching it.
        self._spec = config.draft_model is not None
        if self._spec:
            dc = config.draft_model
            if dc.vocab_size != c.vocab_size:
                raise ValueError(
                    "draft_model vocab_size must match the target's")
            if config.spec_tokens < 2:
                raise ValueError("spec_tokens must be >= 2 (1 draft + "
                                 "1 verified token minimum)")
            if draft_params is None:
                draft_params = init(
                    jax.random.PRNGKey(config.seed + 1), dc)
            self.draft_params = draft_params
            self.draft_cache_k, self.draft_cache_v = self._fresh_cache(dc)
        scratch = 0
        if self._spec:
            scratch = max(scratch, config.spec_tokens)
        if config.chunked_prefill_tokens > 0:
            scratch = max(scratch, config.chunked_prefill_tokens)
        if config.multi_step > 1:
            scratch = max(scratch, config.multi_step)
        self._pos_limit = config.max_seq - 1 - scratch
        if self._pos_limit < 1:
            raise ValueError(
                f"max_seq={config.max_seq} leaves no usable positions "
                f"after the {scratch}-row scratch region")
        # Plain engines (scratch 0) park idle slots at row 0: idle
        # slots hold no live rows and the next occupant's prefill
        # insert overwrites row 0, so the legacy park keeps the full
        # max_seq-1 context. With a scratch region, parking moves
        # there because a PREFILLING slot's rows below its position
        # are live history.
        self._dense_park = config.max_seq - 1 if scratch else 0
        self.slots = [_Slot(i) for i in range(config.max_batch)]
        self.waiting: List[GenerationRequest] = []
        # disaggregated requests: (request, ks, vs, prompt_len, token)
        self._prefilled_waiting: List[tuple] = []
        self._lock = locktrace.traced_lock("llm.engine")
        self.total_generated = 0
        self._step_counter = 0
        # The dense step's per-slot state as the last decode program
        # returned it, the slots it holds live, and whether a slot has
        # changed hands since (a request admitted, ended, found
        # cancelled; another step program run). A dense step that finds
        # the flag set, or that is to run other slots than the state
        # holds live (beside chunked prefill the dense step takes the
        # adapter and logprobs requests only, and all of them again
        # afterwards), builds the state from the slots and sends it,
        # once. Otherwise it sends nothing.
        self._state = None
        self._state_slots: tuple = ()
        self._state_stale = True
        # The dense step launched and not read (``_Flight``), None when
        # the device holds nothing of the dense step's. While one is in
        # flight and ``_may_launch_ahead`` allows, the next is launched
        # from ``_state`` before this one is read; slots then change
        # hands by ``_park`` / ``_seat`` on the device, not by a gather.
        self._flight: Optional[_Flight] = None
        # set by whoever brings the stepper something to look at while
        # it holds (add_request, add_prefilled, cancel)
        self._arrived = threading.Event()
        # what the hold reckons with, all observed on the stepper's
        # clock: when its last blocking read returned (the device had
        # finished everything up to there), the device time of the last
        # decode steps, and the host's last times from the end of a hold
        # to the return of the launch behind it
        self._device_free_at = 0.0
        self._step_device_s: collections.deque = collections.deque(
            maxlen=_STEP_SAMPLES)
        self._launch_lead_s: collections.deque = collections.deque(
            maxlen=_STEP_SAMPLES)
        # and the host's time for one admission behind a step in flight
        self._admit_cost_s: collections.deque = collections.deque(
            maxlen=_STEP_SAMPLES)
        # ``_park`` and ``_seat`` not compiled yet, and the device token
        # to compile ``_seat`` on (``_warm_edits``)
        self._edits_cold, self._warm_token = True, None
        self.decode_launches = {"ahead": 0, "in_order": 0}
        self.discarded_tokens = 0
        self.prefill_tokens = {"real": 0, "pad": 0}
        self.decode_steps = 0     # dense decode programs launched
        self.state_uploads = 0    # of them, with a state from the host
        # rows of a layer's KV cache those programs' attention covered
        # and did not: the decode kernel reads whole blocks up to a
        # slot's position (ops/attention.py), the XLA form every row
        self._kv_block = _attention_op.decode_block_rows(
            config.max_seq, *self._family.kv_row_shape(c)) or config.max_seq
        self.decode_kv_rows = {"read": 0, "skipped": 0}
        # slots x recurrent layers of those programs, by what became of
        # the slot's state; a family that moves every slot's counts none
        self._state_layers = (c.n_mamba_layers
                              if self._family.skips_parked_state else 0)
        self.state_slots = {"moved": 0, "parked": 0}
        # decode programs launched, by the sampler's branches their live
        # slots engaged, and the branches of the slots last gathered
        self.sampler_steps = {"greedy": 0, "topk": 0, "full": 0}
        self._sampler_paths: tuple = ("greedy",)
        # prompts admitted through a prefill of their own, and how many
        # of them were launched under the prefill of the one before
        self.admissions = 0
        self.admissions_overlapped = 0
        # what the family's expert layers count on the device (picks
        # and slots): a small array every prefill and decode program
        # takes and returns one further, NOT donated, so that the
        # metrics flush can read whichever it finds; None for a family
        # with no routed experts
        self._expert_counts = self._fresh_expert_counts()
        self._mbuf = _MetricsBuffer(self)
        for kind, nbytes in self.cache_bytes.items():
            self._mbuf.set(ENGINE_CACHE_BYTES, float(nbytes),
                           {"kind": kind})
        self._admitted_last_step = 0
        # step() calls so far: the number an engine.step span and its
        # child spans share
        self._steps = 0
        self._account = account = _StepperAccount()
        # the process's stall watch looks at the account each tick: a
        # stepper that sits in one phase past its threshold is a
        # ``thread.held`` episode (the probe keeps the account alone)
        probe = _flight.add_probe("stepper", account.probe,
                                  lambda: account.thread)
        weakref.finalize(self, _flight.remove_probe, probe)
        # multi-LoRA bank: slot 0 is the all-zero base adapter, so
        # "no adapter" needs no conditional in the decode program
        self._adapters: Dict[str, int] = {}
        self._adapter_prefill: Dict[str, Any] = {}
        self._next_adapter_slot = 1  # slot 0 = base (all-zero)
        if config.max_loras > 0:
            n, r, hd = config.max_loras + 1, config.lora_rank, c.head_dim
            self.lora_bank = {
                "A_q": jnp.zeros((n, c.n_layers, c.dim, r), c.dtype),
                "B_q": jnp.zeros((n, c.n_layers, r, c.n_heads * hd),
                                 c.dtype),
                "A_v": jnp.zeros((n, c.n_layers, c.dim, r), c.dtype),
                "B_v": jnp.zeros((n, c.n_layers, r, c.n_kv_heads * hd),
                                 c.dtype),
                # per-adapter scale folded into B at registration
                "scale": jnp.asarray(1.0, c.dtype),
            }
        else:
            self.lora_bank = None

        max_k = min(config.max_top_k, c.vocab_size)
        lp_k = min(20, c.vocab_size)  # static top-logprobs width
        self._lp_k = lp_k

        sample_tokens = functools.partial(_sample_tokens, max_k=max_k)

        def decode(params, cache, state, base_key, lora_bank, bias,
                   counts=None, want_lp=False):
            """One token for every live slot. ``state`` ([7, B] int32,
            rows _TOKEN.._STEP) comes back as the next step's: a live
            slot's sampled token and its position one further, a
            parked slot's as they were (token 0 at ``_dense_park``),
            the counter one up, so it equals what the host would gather
            for the next step."""
            tokens, pos, live = state[_TOKEN], state[_POS], state[_LIVE]
            temp = jax.lax.bitcast_convert_type(state[_TEMP], jnp.float32)
            logits, cache, counted = fam.decode_step(
                params, tokens, jax.tree.unflatten(cache_def, cache), pos,
                live, c, lora_bank, state[_LORA])
            if counted is not None:
                counts = counts + counted
            cache = jax.tree.leaves(cache)
            key = jax.random.fold_in(base_key, state[_STEP, 0])
            tok = sample_tokens(logits, temp, state[_TOPK], key, bias, live)
            state = state.at[_TOKEN].set(tok * live).at[_POS].add(
                live).at[_STEP].add(1)
            if not want_lp:
                # static arg: the no-logprobs program carries none of
                # the log_softmax/top_k work or output buffers
                return (state, None, None, None, counts, *cache)
            # logprobs of the biased (un-temperature-scaled) logits;
            # [B] chosen + [B, lp_k] top alternatives — tiny transfers
            lsm = jax.nn.log_softmax(
                (logits + bias).astype(jnp.float32), axis=-1)
            chosen = jnp.take_along_axis(lsm, tok[:, None], 1)[:, 0]
            top_vals, top_ids = jax.lax.top_k(lsm, lp_k)
            return (state, chosen, top_vals, top_ids, counts, *cache)

        def prefill(params, tokens, length, lora, counts=None):
            logits, entry, counted = fam.prefill(params, tokens, length, c,
                                                 lora)
            if counted is not None:
                counts = counts + counted
            return (logits, counts, *jax.tree.leaves(entry))

        def sample_one(logits, temp, topk, key, bias_row,
                       want_lp=False):
            tok = sample_tokens(
                logits[None, :], jnp.full((1,), temp),
                jnp.full((1,), topk, dtype=jnp.int32), key,
                bias_row[None, :])[0]
            if not want_lp:
                return tok, None, None, None
            lsm = jax.nn.log_softmax(
                (logits + bias_row).astype(jnp.float32))
            chosen = lsm[tok]
            top_vals, top_ids = jax.lax.top_k(lsm, lp_k)
            return tok, chosen, top_vals, top_ids

        def insert(cache, entry, slot):
            # in-place (donated) slot write, leaf by leaf: no whole-cache
            # copy. An entry's leaves come from a batch-1 prefill (for
            # the Llama family ks / vs of [L, 1, bucket, KVH, HD]).
            return insert_slot(cache, entry, slot)

        park_at = self._dense_park

        def park(state, parked):
            """``state`` with the columns that ``parked`` ([B] int32)
            marks as ``_gather_state`` writes an empty slot's: token 0
            at the park row, not live, the counter kept."""
            empty = jnp.zeros_like(state).at[_POS].set(park_at).at[
                _STEP].set(state[_STEP])
            return jnp.where(parked[None, :] > 0, empty, state)

        def seat(state, token, row):
            """``state`` with one slot's column as ``_gather_state``
            writes an admitted request's, its first token taken from
            the device: ``token`` is what ``sample_one`` returned, not
            read. ``row`` (int32): the slot, then position, temperature
            (bit-cast), top-k, adapter and the sampler's counter, which
            every column takes (a prefill's sampling advanced it)."""
            column = jnp.stack([token.astype(jnp.int32), row[1], row[2],
                                row[3], row[4], jnp.int32(1), row[5]])
            state = state.at[_STEP].set(row[5])
            return jax.lax.dynamic_update_slice(
                state, column[:, None], (0, row[0]))

        # neither donates: the state a step returned is still to be read
        self._park = jax.jit(park)
        self._seat = jax.jit(seat)
        self._decode = jax.jit(decode, donate_argnums=(1,),
                               static_argnames=("want_lp",))
        self._prefill = jax.jit(prefill)
        self._sample_one = jax.jit(sample_one,
                                   static_argnames=("want_lp",))
        self._insert = jax.jit(insert, donate_argnums=(0,))

        if config.enable_prefix_caching:
            # token-tuple -> (ks, vs, prompt_len); LRU, device-resident
            self._prefix_cache = collections.OrderedDict()
            self.prefix_hits = 0
            self.prefix_misses = 0

            def suffix_prefill(tparams, cks, cvs, chunk, start, *,
                               bucket):
                """Seed-and-score in ONE program: pad/crop the cached
                prefix KV to the target bucket and verify the suffix
                chunk at the boundary. Fusing the seeding in keeps the
                hit path at a single dispatch — separate zeros +
                at[].set copies cost more than the full prefill they
                replace."""
                bp = cks.shape[2]
                if bp < bucket:
                    pad = ((0, 0), (0, 0), (0, bucket - bp),
                           (0, 0), (0, 0))
                    base_k = jnp.pad(cks, pad)
                    base_v = jnp.pad(cvs, pad)
                else:
                    base_k = cks[:, :, :bucket]
                    base_v = cvs[:, :, :bucket]
                return llama_verify_step(tparams, chunk, base_k,
                                         base_v, start, c)

            self._suffix_prefill = jax.jit(suffix_prefill,
                                           static_argnames=("bucket",))
        else:
            self._prefix_cache = None

        if config.chunked_prefill_tokens > 0:
            C = config.chunked_prefill_tokens
            if self._spec:
                raise ValueError("chunked_prefill_tokens and "
                                 "draft_model are mutually exclusive")
            if config.enable_prefix_caching:
                raise ValueError("chunked_prefill_tokens and "
                                 "enable_prefix_caching are mutually "
                                 "exclusive")
            def chunk_prefill(tparams, ck, cv, chunk, pos, last_idx,
                              temp, topk, base_key, step, bias):
                """One C-token prefill chunk for every prefilling slot
                (idle/decoding slots park their writes); returns the
                sampled first token per slot, used only for slots
                whose prompt completed this round."""
                logits, ck, cv = llama_verify_step(
                    tparams, chunk, ck, cv, pos, c)
                sel = jnp.take_along_axis(
                    logits, last_idx[:, None, None], axis=1)[:, 0]
                key = jax.random.fold_in(base_key, step)
                tok = sample_tokens(sel, temp, topk, key, bias)
                return tok, ck, cv

            self._chunk_prefill = jax.jit(chunk_prefill,
                                          donate_argnums=(1, 2))

        if config.multi_step > 1:
            if self._spec:
                raise ValueError(
                    "multi_step and draft_model are mutually exclusive")
            K = config.multi_step

            def decode_multi(params, cache_k, cache_v, tokens, pos,
                             temp, topk, base_key, step,
                             lora_bank, lora_idx, bias):
                """K fused decode iterations — one dispatch for up to
                K tokens per slot."""
                round_key = jax.random.fold_in(base_key, step)

                def body(carry, i):
                    tok, ck, cv = carry
                    logits, ck, cv = llama_decode_step(
                        params, tok, ck, cv, pos + i, c,
                        lora_bank=lora_bank, lora_idx=lora_idx)
                    key = jax.random.fold_in(round_key, i)
                    nxt = sample_tokens(logits, temp, topk, key, bias)
                    return (nxt, ck, cv), nxt

                (_, ck, cv), toks = jax.lax.scan(
                    body, (tokens, cache_k, cache_v), jnp.arange(K))
                return toks, ck, cv              # toks: [K, B]

            self._decode_multi = jax.jit(decode_multi,
                                         donate_argnums=(1, 2))

        if self._spec:
            dc = config.draft_model
            n_draft = config.spec_tokens - 1

            def draft_propose(dparams, ck, cv, token0, pos0):
                """All greedy draft steps fused into ONE program
                (lax.scan) — one device dispatch per round instead of
                G-1, which matters when decode is dispatch-bound.

                The scan runs G (not G-1) steps: the extra step's
                OUTPUT is discarded, but it writes d_{G-1}'s K/V into
                the draft cache — on full acceptance the next round
                starts at pos+G, and without that row the draft would
                attend a junk row forever after, silently collapsing
                acceptance exactly in the high-acceptance regime."""
                def body(carry, i):
                    tok, ck, cv = carry
                    logits, ck, cv = llama_decode_step(
                        dparams, tok, ck, cv, pos0 + i, dc)
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (nxt, ck, cv), nxt

                (_, ck, cv), drafts = jax.lax.scan(
                    body, (token0, ck, cv), jnp.arange(n_draft + 1))
                return drafts[:n_draft], ck, cv   # drafts: [G-1, B]

            def draft_sync(dparams, ck, cv, state):
                """Dense-path companion: write the fed tokens' K/V into
                the draft cache (output discarded) so dense fallback
                rounds don't leave gaps that desync the draft. Reads
                the state the dense step was given."""
                _logits, ck, cv = llama_decode_step(
                    dparams, state[_TOKEN], ck, cv, state[_POS], dc)
                return ck, cv

            def verify(tparams, ck, cv, chunk, pos, temp, topk,
                       base_key, step, bias):
                logits, ck, cv = llama_verify_step(
                    tparams, chunk, ck, cv, pos, c)
                greedy = jnp.argmax(logits + bias[:, None, :],
                                    axis=-1).astype(jnp.int32)
                key = jax.random.fold_in(base_key, step)
                first = sample_tokens(logits[:, 0], temp, topk, key,
                                      bias)
                return greedy, first, ck, cv

            self._draft_propose = jax.jit(draft_propose,
                                          donate_argnums=(1, 2))
            self._draft_sync = jax.jit(draft_sync, donate_argnums=(1, 2))
            self._verify = jax.jit(verify, donate_argnums=(1, 2))
            self._draft_prefill = jax.jit(
                lambda p, t: llama_prefill(p, t, dc))

    # ------------------------------------------------------------------
    @property
    def cache_k(self):
        """The Llama family's keys: the first leaf of ``cache``."""
        return self.cache[0]

    @property
    def cache_v(self):
        """The Llama family's values: the second leaf of ``cache``."""
        return self.cache[1]

    def register_adapter(self, name: str, lora_params) -> None:
        """Install a LoRA adapter into the bank under ``name``
        (reference: vLLM add_lora / serve model multiplexing). The
        adapter's alpha/rank scale is folded into its B matrices so the
        decode program stays scale-free."""
        if self.lora_bank is None:
            raise ValueError("engine built with max_loras=0")
        jnp = self._jnp
        scale = float(lora_params.get("scale", 1.0))
        folded = dict(lora_params)
        folded["B_q"] = lora_params["B_q"] * scale
        folded["B_v"] = lora_params["B_v"] * scale
        folded["scale"] = jnp.asarray(1.0, self.config.model.dtype)
        rank = int(folded["A_q"].shape[-1])
        bank_rank = self.config.lora_rank
        if rank > bank_rank:
            raise ValueError(
                f"adapter rank {rank} exceeds the engine's lora_rank "
                f"{bank_rank}")
        if rank < bank_rank:
            # zero-pad up to the bank's static rank: the extra zero
            # columns are exactly the identity, so the math is unchanged
            pad = bank_rank - rank
            for part, axis in (("A_q", -1), ("A_v", -1),
                               ("B_q", -2), ("B_v", -2)):
                widths = [(0, 0)] * folded[part].ndim
                widths[axis] = (0, pad)
                folded[part] = jnp.pad(folded[part], widths)
        # Full shape validation BEFORE reserving anything — a failed
        # registration must not leak a bank slot.
        for part in ("A_q", "B_q", "A_v", "B_v"):
            want = self.lora_bank[part].shape[1:]
            got = tuple(folded[part].shape)
            if got != want:
                raise ValueError(
                    f"adapter {part} shape {got} does not match the "
                    f"engine's bank slot shape {want} (built for a "
                    "different model config?)")
        # Install under the lock, publishing a COMPLETE new bank dict in
        # one reference swap: the serve stepper reads self.lora_bank
        # once per step, so it sees either the old or the new bank,
        # never mismatched A/B factors; the lock serializes concurrent
        # registrations so neither's slot write is lost.
        with self._lock:
            idx = self._adapters.get(name)
            if idx is None:
                idx = self._next_adapter_slot
                if idx > self.config.max_loras:
                    raise ValueError(
                        f"LoRA bank full ({self.config.max_loras}); "
                        "raise max_loras")
                self._next_adapter_slot += 1
            new_bank = dict(self.lora_bank)
            for part in ("A_q", "B_q", "A_v", "B_v"):
                new_bank[part] = self.lora_bank[part].at[idx].set(
                    folded[part])
            self.lora_bank = new_bank
            self._adapter_prefill[name] = folded
            self._adapters[name] = idx

    def _adapter_index(self, request: GenerationRequest) -> int:
        if request.adapter is None:
            return 0
        idx = self._adapters.get(request.adapter)
        if idx is None:
            raise ValueError(f"unknown LoRA adapter {request.adapter!r}")
        return idx

    def prefill_only(self, prompt_ids: List[int], *,
                     temperature: float = 0.0, top_k: int = 0,
                     adapter: Optional[str] = None,
                     logit_bias: Optional[Dict[int, float]] = None,
                     guided: Optional[Any] = None):
        """Prefill without occupying a decode slot — the PREFILL side of
        prefill/decode disaggregation (reference: serve/llm
        prefill-decode disagg deployments). Returns numpy
        (ks, vs, prompt_len, first_token): the KV block ships through
        the object plane to a decode engine's add_prefilled().

        ``guided``: a TokenConstraint — the FIRST token is sampled
        under its start-state mask; the decode engine re-walks the
        automaton from the start state when it adopts the request, so
        prefill/decode stay consistent without shipping opaque state.
        """
        self._refuse_disagg("prefill_only")
        limit = self._pos_limit
        ids = list(prompt_ids)[-limit:]
        if adapter is not None and adapter not in self._adapters:
            raise ValueError(f"unknown LoRA adapter {adapter!r}")
        self._validate_logit_bias(logit_bias)
        fake = GenerationRequest(prompt_ids=[], logit_bias=logit_bias,
                                 guided=guided)
        self._validate_guided(fake)
        launched = self._run_prefill(ids, adapter, temperature, top_k,
                                     biased_as=fake)
        token, _lp = self._read_first_token(launched.sampled)
        ks, vs = launched.entry
        return (np.asarray(ks), np.asarray(vs), len(ids), token)

    def add_prefilled(self, request: GenerationRequest, ks, vs,
                      prompt_len: int, first_token: int) -> GenerationRequest:
        """DECODE side of disaggregation: adopt a request whose prefill
        ran elsewhere — the KV block is inserted into a free slot at the
        next admit, skipping local prefill entirely."""
        self._refuse_disagg("add_prefilled")
        if request.logprobs is not None:
            raise ValueError(
                "logprobs are not supported on the disaggregated "
                "decode path (the first token's distribution lives on "
                "the prefill engine)")
        if prompt_len > self._pos_limit:
            # pos_limit, not max_seq-1: a speculative engine reserves
            # its scratch rows, and admitting past the limit would
            # end the request after exactly one token
            raise ValueError("prefilled prompt exceeds this engine's "
                             "position limit")
        if ks.shape[2] > self.config.max_seq:
            raise ValueError(
                f"prefilled KV bucket ({ks.shape[2]}) exceeds this "
                f"engine's max_seq ({self.config.max_seq})")
        self._validate_logit_bias(request.logit_bias)
        self._validate_guided(request)
        if request.adapter is not None:
            self._adapter_index(request)  # fail fast: an unknown
            # adapter raising inside step() would fail_all the replica
        if request.top_k > self.config.max_top_k:
            request.top_k = self.config.max_top_k
        request.t_submit = time.perf_counter()
        with self._lock:
            self._prefilled_waiting.append(
                (request, ks, vs, prompt_len, first_token))
        self._arrived.set()
        return request

    def add_request(self, request: GenerationRequest) -> GenerationRequest:
        request.t_submit = time.perf_counter()
        self._validate_logit_bias(request.logit_bias)
        self._validate_guided(request)
        limit = self._pos_limit
        if len(request.prompt_ids) > limit:
            request.prompt_ids = request.prompt_ids[-limit:]
        if request.adapter is not None:
            if self._family.dense_only:
                raise ValueError(
                    "adapter: LoRA is implemented for the Llama family's "
                    "projections, not for a "
                    f"{type(self.config.model).__name__}")
            self._adapter_index(request)  # fail fast on unknown names
        if request.top_k > self.config.max_top_k:
            # the sampler's static width bounds per-request top-k; make
            # the effective value visible rather than silently narrower
            request.top_k = self.config.max_top_k
        if request.logprobs is not None:
            request.logprobs = min(max(int(request.logprobs), 0),
                                   self._lp_k)
        with self._lock:
            cap = self.config.max_waiting_requests
            if cap > 0 and len(self.waiting) >= cap:
                waiting = len(self.waiting)
            else:
                waiting = None
                self.waiting.append(request)
        if waiting is not None:
            raise EngineSaturatedError(waiting, cap)
        self._arrived.set()     # a stepper that holds looks now
        return request

    def has_work(self) -> bool:
        with self._lock:
            return (bool(self.waiting) or bool(self._prefilled_waiting)
                    or self._flight is not None
                    or any(s.request is not None for s in self.slots))

    def _free_slots(self) -> List[_Slot]:
        return [s for s in self.slots if s.request is None]

    def _admit_prefilled(self) -> None:
        """Adopt disaggregated requests: their KV arrives ready-made
        from a prefill engine; just insert into a free slot."""
        while True:
            with self._lock:
                if not self._prefilled_waiting:
                    return
                free = self._free_slots()
                if not free:
                    return
                request, ks, vs, plen, tok = self._prefilled_waiting.pop(0)
                slot = free[0]
                slot.request = request
            self._state_stale = True  # graftlint: disable=GL001  # stepper-thread-only
            self._note_admitted(request)
            self._install_bias(request, slot.index)
            entry = self._upload(ks, vs)
            self.cache = self._insert(self.cache, entry, slot.index)
            del entry
            if self._spec:
                # disagg ships only the TARGET KV; rebuild the draft's
                # prefix locally (draft prefill is cheap). The draft
                # must see EXACTLY the plen tokens the target KV was
                # built from — the disagg protocol may adopt with
                # empty/shorter ids ("KV already computed"), in which
                # case this slot decodes dense (draft_ready=False)
                # rather than speculating on a garbage prefix.
                ids = list(request.prompt_ids)
                if len(ids) >= plen:
                    self._draft_prefill_slot(ids[-plen:], slot.index)
                    slot.draft_ready = True
                else:
                    slot.draft_ready = False
            slot.next_token = tok
            slot.pos = plen
            self._emit(slot, tok)

    def _note_admitted(self, request: GenerationRequest) -> None:
        """The stepper popped ``request`` from a waiting queue: its
        queue stage ends here and its prefill stage starts."""
        request.t_admit = now = time.perf_counter()
        if request.t_submit is None:
            return
        waited = max(0.0, now - request.t_submit)
        self.record_stage("queue", waited)
        rec = _flight.RECORDER
        if rec is not None:
            waited_ns = int(waited * 1e9)
            rec.record("serve", "request_queue", rec.clock() - waited_ns,
                       waited_ns, {"req": request.request_id,
                                   "step": self._steps})

    def _span(self, name: str, **args) -> "_flight.span":
        """A phase of the current step, on the profiler's clock and in
        the flight recorder (category serve), tagged with the step;
        on the stepper thread also a phase of its account, and the one
        place that switches it (prefill_only runs on request threads)."""
        if threading.get_ident() != self._account.thread:
            return _flight.span("serve", name, step=self._steps, **args)
        return _StepperSpan(self._account, name, step=self._steps, **args)

    def idling(self) -> "_flight.span":
        """For the loop that drives step(), around its wait for work:
        the stepper's time between steps is in the account and on the
        trace as engine.wait."""
        self._account.bind()
        return self._span("engine.wait")

    def _sharding_beside(self, params):
        """Where the engine keeps what it sends and feeds back: the one
        device the params are on (the default device for params that
        are on none yet), or replicated over their mesh when they are
        sharded over several."""
        jax = self._jax
        sharding = getattr(jax.tree_util.tree_leaves(params)[0],
                           "sharding", None)
        if sharding is None:
            return jax.sharding.SingleDeviceSharding(jax.devices()[0])
        if len(sharding.device_set) == 1:
            return jax.sharding.SingleDeviceSharding(
                next(iter(sharding.device_set)))
        return jax.sharding.NamedSharding(
            sharding.mesh, jax.sharding.PartitionSpec())

    def _fresh_cache(self, model) -> list:
        """The leaves of an empty serving cache of ``model``'s family,
        committed to the engine's device (see ``_on_device``)."""
        jax = self._jax
        return jax.device_put(
            jax.tree.leaves(family_of(model).init_cache(
                model, self.config.max_batch, self.config.max_seq)),
            self._on_device)

    def _fresh_bias(self) -> list:
        """The slots' [B, V] bias rows and the [V] row of a request that
        biases nothing, zeroed and committed like an uploaded row (see
        ``_on_device``): a biased and an unbiased admission run the same
        ``set_bias_row`` and ``sample_one``."""
        jnp = self._jnp
        c = self.config
        vocab = c.model.vocab_size
        return self._jax.device_put(
            [jnp.zeros((c.max_batch, vocab), jnp.float32),
             jnp.zeros((vocab,), jnp.float32)], self._on_device)

    def _fresh_expert_counts(self):
        names = self._family.expert_counts
        if not names:
            return None
        return self._jax.device_put(
            self._jnp.zeros((len(names),), self._jnp.uint32),
            self._on_device)

    def _upload(self, *arrays) -> list:
        """Host arrays of one step onto the device, committed there
        (see ``_on_device``); the time it takes is the step's upload
        time. Callers ``del`` the results once the program that reads
        them is launched, as call-site temporaries would go: a device
        array dropped after the step's read-back is released on the
        stepper thread while the device sits idle (0.4 ms each on a
        v5e); dropped earlier, its release is paid inside the next
        step's uploads, a little cheaper in sum."""
        with self._span("engine.upload"):
            return [self._jax.device_put(a, self._on_device)
                    for a in arrays]

    def _readback(self, *arrays) -> list:
        """Device results as numpy arrays. These reads block the
        stepper until the device has finished the program before them,
        so every step program's and the prefill's go through here and
        the time waited is counted once: a step's host time is its
        wall time less this."""
        with self._span("engine.readback"):
            read = [np.asarray(a) for a in arrays]
        account = self._account
        if threading.get_ident() == account.thread:
            # the span's end switched the account: its clock then is
            # when the device was seen to have finished up to here
            self._device_free_at = account.t  # graftlint: disable=GL001  # stepper-thread-only
        return read

    def _note_kv_rows(self, positions: List[int]) -> None:
        """The cache rows the dense step just launched covers, from the
        live slots' positions as it was given them, and whose recurrent
        state it moved (``_state_layers``): counted while the device
        runs it."""
        block = self._kv_block
        live = len(positions)
        parked = self.config.max_batch - live
        read = block * (sum(p // block for p in positions) + live
                        + parked * (self._dense_park // block + 1))
        skipped = self.config.max_batch * self.config.max_seq - read
        # stepper-thread-only
        self.decode_kv_rows["read"] += read  # graftlint: disable=GL001
        self.decode_kv_rows["skipped"] += skipped  # graftlint: disable=GL001
        self._mbuf.inc(ENGINE_DECODE_KV_ROWS, float(read), {"kind": "read"})
        self._mbuf.inc(ENGINE_DECODE_KV_ROWS, float(skipped),
                       {"kind": "skipped"})
        if self._state_layers:
            for kind, slots in (("moved", live), ("parked", parked)):
                n = slots * self._state_layers
                self.state_slots[kind] += n  # graftlint: disable=GL001
                self._mbuf.inc(ENGINE_STATE_SLOTS, float(n), {"kind": kind})

    def _note_prefill_tokens(self, real: int, pad: int) -> None:
        """What one prefill program computed: the prompt's own
        positions and the ones its bucket added (wasted work)."""
        with self._lock:
            self.prefill_tokens["real"] += real
            self.prefill_tokens["pad"] += pad
        self._mbuf.inc(ENGINE_PREFILL_TOKENS, float(real), {"kind": "real"})
        self._mbuf.inc(ENGINE_PREFILL_TOKENS, float(pad), {"kind": "pad"})

    @staticmethod
    def _refuse_beyond_dense(config: EngineConfig, cache_is: str) -> None:
        """A family with a word on its cache (``ModelFamily.dense_only``:
        ``cache_is``) runs the dense path only. The other step programs
        and the prefix cache rewrite, keep or ship rows of keys and
        values as the Llama family lays them out: run over a consumed
        state they would corrupt it, and a latent row is neither a key
        nor a value, so the engine says so at construction."""
        family = type(config.model).__name__
        rows = (f"needs the Llama family's cache, rows of keys and values "
                f"that can be written again or kept apart; the cache of a "
                f"{family} {cache_is}")
        llama_only = (f"is implemented for the Llama family's "
                      f"projections, not for a {family}")
        for option, asked, why in (
                ("draft_model", config.draft_model is not None, rows),
                ("multi_step", config.multi_step > 1, rows),
                ("enable_prefix_caching", config.enable_prefix_caching,
                 rows),
                ("chunked_prefill_tokens",
                 config.chunked_prefill_tokens > 0, rows),
                ("max_loras", config.max_loras > 0, llama_only),
                ("quantization", config.quantization is not None,
                 llama_only)):
            if asked:
                raise ValueError(f"{option} {why}")

    def _refuse_disagg(self, what: str) -> None:
        if self._family.dense_only:
            raise ValueError(
                f"{what} ships a prompt's rows of keys and values; the "
                f"cache of a {type(self.config.model).__name__} "
                f"{self._family.dense_only}, which the disaggregated "
                "path does not carry")

    def _call_program(self, name: str, jitted, *args, **static):
        """Run a jitted program, keeping the abstract signature of its
        first call so stats() can lower it again and name the Pallas
        kernels it holds."""
        if name not in self._program_sigs:
            jax = self._jax
            sig = (jitted, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if isinstance(x, (jax.Array, np.ndarray)) else x,
                args), static)
            with self._lock:
                self._program_sigs[name] = sig
        return jitted(*args, **static)

    def _run_prefill(self, ids: List[int], adapter: Optional[str],
                     temperature: float, top_k: int,
                     biased_as: Optional[GenerationRequest] = None,
                     want_logprobs: bool = False) -> _Launched:
        """Shared prefill: bucket/pad the prompt, launch the jitted
        prefill, then queue the first token's sampling behind it. Both
        the colocated admit path and prefill_only (disaggregation) call
        this — one copy, so the exact-parity guarantee between the two
        modes can't drift.

        The device starts on the prompt before the host does anything
        the prefill does not need: ``biased_as``'s [V] row is built and
        sent while the prefill runs, once, and the one device array
        samples the first token here and is the slot's row for the
        caller to install. Nothing here waits for the device: the
        caller reads the token with ``_read_first_token`` when it has
        queued what else it has for the device."""
        use_cache = self._prefix_cache is not None and adapter is None
        hit = self._match_prefix(ids) if use_cache else None
        if hit is not None:
            # suffix chunk must fit below max_seq alongside the prefix
            plen_p = hit[2]
            if plen_p + self._bucket_len(len(ids) - plen_p) > \
                    self.config.max_seq:
                hit = None
        if hit is None:
            if use_cache:
                with self._lock:
                    self.prefix_misses += 1
            padded = self._pad_bucket(ids)
            lora = self._adapter_prefill.get(adapter) if adapter else None
            (tokens_dev,) = self._upload(padded)
            with self._span("engine.launch"):
                logits, self._expert_counts, *entry = self._call_program(
                    f"prefill_{padded.shape[1]}", self._prefill,
                    self.params, tokens_dev, np.int32(len(ids)), lora,
                    self._expert_counts)
            # a family that is told the length may return that
            # position's row alone
            last = min(len(ids), logits.shape[1]) - 1
            self._note_prefill_tokens(len(ids), padded.shape[1] - len(ids))
        else:
            # suffix-only prefill: ONE fused program pads the cached
            # prefix KV to the target bucket and scores the suffix
            # chunk at the prefix boundary. Donor rows past the match
            # point may hold ANOTHER prompt's live KV (a longest-
            # common-prefix hit copies the whole entry) — they never
            # leak only because every row at or above plen_p is
            # rewritten (by this suffix chunk or a later decode)
            # before it becomes attendable. Do not weaken that
            # invariant.
            with self._lock:
                self.prefix_hits += 1
            cks, cvs, plen_p = hit
            suffix = ids[plen_p:]
            chunk_len = self._bucket_len(len(suffix))
            bucket = self._bucket_len(plen_p + chunk_len)
            chunk = np.zeros((1, chunk_len), dtype=np.int32)
            chunk[0, : len(suffix)] = suffix
            chunk_dev, start_dev = self._upload(
                chunk, np.asarray([plen_p], dtype=np.int32))
            with self._span("engine.launch"):
                logits, *entry = self._suffix_prefill(
                    self.params, cks, cvs, chunk_dev, start_dev,
                    bucket=bucket)
            last = len(suffix) - 1
            self._note_prefill_tokens(len(suffix),
                                      chunk_len - len(suffix))
        t_launched = time.perf_counter()
        bias_dev = self._bias_on_device(biased_as)
        # stepper-thread-only RNG state
        self._step_counter += 1  # graftlint: disable=GL001
        with self._span("engine.launch"):
            sampled = self._sample_one(
                logits[0, last], float(temperature), int(top_k),
                self._jax.random.fold_in(self._base_key,
                                         self._step_counter),
                bias_dev, want_lp=want_logprobs)
        if use_cache:
            self._store_prefix(ids, *entry)
        return _Launched(entry, bias_dev, sampled, t_launched)

    def _read_first_token(self, sampled: tuple):
        """Wait for what ``_run_prefill`` sampled: (token, its
        logprobs or None). Only a logprobs request reads more than the
        token."""
        token, chosen, top_vals, top_ids = sampled
        if chosen is None:
            (token,) = self._readback(token)
            return int(token), None
        token, chosen, top_vals, top_ids = self._readback(
            token, chosen, top_vals, top_ids)
        return int(token), (float(chosen), top_vals, top_ids)

    def _validate_logit_bias(self, logit_bias) -> None:
        """Reject out-of-vocab ids on the CALLER's thread — every
        admission entry point (add_request, add_prefilled,
        prefill_only) funnels through this, because a raise inside the
        stepper's _admit would fail_all the whole replica, and a
        negative id would silently wrap to the vocab tail in numpy
        indexing."""
        if not logit_bias:
            return
        vocab = self.config.model.vocab_size
        for tid in logit_bias:
            if not 0 <= int(tid) < vocab:
                raise ValueError(
                    f"logit_bias token id {tid} outside vocab "
                    f"[0, {vocab})")

    def _validate_guided(self, request: GenerationRequest) -> None:
        """Caller-thread validation + state init for guided requests
        (same fail-fast rationale as _validate_logit_bias)."""
        if request.guided is None:
            return
        if request.guided.vocab_size > self.config.model.vocab_size:
            raise ValueError(
                f"guided constraint vocab ({request.guided.vocab_size}) "
                f"exceeds model vocab ({self.config.model.vocab_size})")
        if request.guided_state is None:
            request.guided_state = request.guided.start_state()

    @staticmethod
    def _has_dynamic_bias(request: GenerationRequest) -> bool:
        """True when the slot's bias row depends on what has been
        generated so far (guided mask / repetition penalties) and must
        be refreshed between steps — such requests are excluded from
        the fused multi-token fast paths."""
        return (request.guided is not None
                or request.presence_penalty != 0.0
                or request.frequency_penalty != 0.0)

    def _bias_row(self, request: GenerationRequest) -> np.ndarray:
        """Dense [V] f32 bias row from the request's sparse
        logit_bias (values clamped to the OpenAI +-100 range; ids
        outside the vocab rejected at add_request), combined with
        presence/frequency penalties over the tokens generated so far
        and with the guided-decoding mask for the request's CURRENT
        automaton state (-1e9 on disallowed ids — far below every
        other term, so nothing resurrects a grammar-banned token)."""
        vocab = self.config.model.vocab_size
        row = np.zeros(vocab, dtype=np.float32)
        for tid, val in (request.logit_bias or {}).items():
            row[int(tid)] = float(np.clip(val, -100.0, 100.0))
        if (request.presence_penalty or request.frequency_penalty) \
                and request.output_ids:
            ids, counts = np.unique(
                np.asarray(request.output_ids, dtype=np.int64),
                return_counts=True)
            keep = (ids >= 0) & (ids < vocab)
            ids, counts = ids[keep], counts[keep]
            row[ids] -= (request.presence_penalty
                         + request.frequency_penalty * counts)
        if request.guided is not None and request.guided_state is not None:
            mask = request.guided.token_mask(request.guided_state)
            penalty = np.full(vocab, -1e9, dtype=np.float32)
            penalty[: mask.shape[0]][mask] = 0.0
            row = row + penalty
        return row

    def _bias_on_device(self, request: Optional[GenerationRequest]):
        """``request``'s [V] row on the device, built and sent here;
        the shared zero row, with no host build or copy, for a request
        that biases nothing (or none)."""
        if request is None or not (request.logit_bias
                                   or self._has_dynamic_bias(request)):
            return self._zero_bias_row
        with self._span("engine.bias"):
            (row,) = self._upload(self._bias_row(request))
        return row

    def _install_bias(self, request: GenerationRequest,
                      slot_index: int, row=None) -> None:
        """Make ``row`` (by default the request's, built now) the
        slot's row for the decode steps to come."""
        if row is None:
            row = self._bias_on_device(request)
        self._bias = self._set_bias(self._bias, row, np.int32(slot_index))

    def _bucket_len(self, n: int) -> int:
        bucket = 1
        while bucket < n:
            bucket *= 2
        return min(bucket, self.config.max_seq)

    def _pad_bucket(self, ids: List[int]) -> np.ndarray:
        """Power-of-two bucket/pad a prompt — ONE copy of the policy so
        target and draft prefills can't drift apart (each distinct
        bucket is its own XLA program)."""
        bucket = self._bucket_len(len(ids))
        padded = np.zeros((1, bucket), dtype=np.int32)
        padded[0, : len(ids)] = ids
        return padded

    # -- prefix caching -------------------------------------------------
    def _match_prefix(self, ids: List[int]):
        """Longest COMMON prefix between ids and any cached prompt.

        Causal attention makes any prefix of a cached KV block valid
        on its own, so two prompts sharing only a system prompt still
        hit (the classic case: cached "A+B1" serves "A+B2" up to the
        shared A). Capped at len(ids)-1 so at least one suffix token
        remains to produce the first-token logits.

        Runs under the engine lock — prefill_only is reachable from
        concurrent replica request threads, and an unlocked
        OrderedDict scan would race _store_prefix's insert/evict.
        The token compare is vectorized (numpy mismatch scan), not a
        Python loop — this sits on the TTFT-critical path.
        """
        ids_arr = np.asarray(ids, dtype=np.int64)
        best_key, best_l = None, 0
        with self._lock:
            for key, (key_arr, _ks, _vs) in self._prefix_cache.items():
                n = min(len(key_arr), len(ids_arr))
                neq = np.nonzero(key_arr[:n] != ids_arr[:n])[0]
                l = int(neq[0]) if neq.size else n
                l = min(l, len(ids) - 1)
                if l > best_l:
                    best_key, best_l = key, l
            if best_key is None or \
                    best_l < self.config.prefix_cache_min_tokens:
                return None
            self._prefix_cache.move_to_end(best_key)
            _key_arr, ks, vs = self._prefix_cache[best_key]
            return ks, vs, best_l

    def _store_prefix(self, ids: List[int], ks, vs) -> None:
        key = tuple(ids)
        if len(key) < self.config.prefix_cache_min_tokens:
            return
        with self._lock:
            if key in self._prefix_cache:
                return
            self._prefix_cache[key] = (
                np.asarray(ids, dtype=np.int64), ks, vs)
            while len(self._prefix_cache) > \
                    self.config.prefix_cache_entries:
                self._prefix_cache.popitem(last=False)

    def _draft_prefill_slot(self, ids: List[int], slot_index: int) -> None:
        """Prefill the DRAFT model's cache for a newly admitted prompt
        so its proposals condition on the real prefix (cheap — the
        draft is small by construction)."""
        jnp = self._jnp
        _logits, ks, vs = self._draft_prefill(
            self.draft_params, jnp.asarray(self._pad_bucket(ids)))
        self.draft_cache_k, self.draft_cache_v = self._insert(
            [self.draft_cache_k, self.draft_cache_v], [ks, vs], slot_index)

    def _admit(self) -> None:
        """Prefill waiting requests into free slots, two deep: the next
        waiting prompt that has a free slot is queued on the device
        before the host waits for the first token of the one before it,
        so the device goes from one prompt's programs to the next's.
        First tokens are read and emitted in admission order. Deeper
        would buy nothing (one prefill outlasts the host's part of the
        next admission several times) and hold more prefill outputs."""
        self._admit_prefilled()
        ahead: Optional[_Admission] = None
        while True:
            with self._lock:
                free = self._free_slots() if self.waiting else None
                if free:
                    request = self.waiting.pop(0)
                    slot = free[0]
                    slot.request = request
            if not free and ahead is None:
                return
            queued = None
            if free:
                self._admitted_last_step += 1  # graftlint: disable=GL001  # stepper-thread-only
                self._note_admitted(request)
                queued = self._prefill_into(slot, request,
                                            overlapped=ahead is not None)
            if ahead is not None:
                # with or without a prompt queued behind it; then the
                # loop looks again, for a prompt that arrived meanwhile
                # or the slot that its ending freed
                self._first_token_out(ahead)
            ahead = queued

    def _prefill_into(self, slot: _Slot, request: GenerationRequest,
                      overlapped: bool) -> Optional[_Admission]:
        """Queue everything the device will run for one admitted
        prompt: its prefill first, then its bias row, its first token's
        sampling and the slot hand-over. Returns what ``_first_token_out``
        finishes; None for a chunked admission, which queues nothing
        (step() runs its chunks) and only books the prompt in."""
        ids = request.prompt_ids
        self._state_stale = True  # graftlint: disable=GL001  # stepper-thread-only
        span = self._span("engine.prefill", req=request.request_id,
                          prompt_len=len(ids),
                          bucket=self._bucket_len(len(ids)))
        C = self.config.chunked_prefill_tokens
        if C > 0 and request.adapter is None \
                and request.logprobs is None:
            # chunked admission: no blocking prefill — step() will
            # advance this prompt one chunk at a time. Every chunk
            # write stays in bounds because add_request truncated
            # the prompt to _pos_limit = max_seq-1-scratch with
            # scratch >= C. LoRA requests lack a chunk-program
            # path and take the blocking prefill below.
            with span:
                self._install_bias(request, slot.index)
            slot.prefilling = True
            slot.prefill_ids = list(ids)
            slot.prefill_pos = 0
            slot.pos = 0
            slot.next_token = 0
            return None
        # open until the first token is out: under a second admission
        # the spans of the two overlap, as their work does
        span.__enter__()
        launched = self._run_prefill(
            ids, request.adapter, request.temperature, request.top_k,
            biased_as=request, want_logprobs=request.logprobs is not None)
        self.admissions += 1  # graftlint: disable=GL001  # stepper-thread-only
        self.admissions_overlapped += overlapped  # graftlint: disable=GL001
        if self._edits_cold:
            self._warm_token = launched.sampled[0]  # graftlint: disable=GL001
        self._mbuf.observe(
            ENGINE_ADMIT_LAUNCH_SECONDS,
            max(0.0, launched.t_launched - request.t_admit),
            {"overlapped": "1" if overlapped else "0"})
        with self._span("engine.launch"):
            self._install_bias(request, slot.index, launched.bias)
            with self._span("engine.insert"):
                self.cache = self._insert(self.cache, launched.entry,
                                          slot.index)
            if self._spec:
                self._draft_prefill_slot(ids, slot.index)
                slot.draft_ready = True
        slot.pos = len(ids)
        return _Admission(slot, launched.sampled, span)

    def _first_token_out(self, admission: _Admission) -> None:
        """Wait for an admitted prompt's first token and emit it; the
        slot hand-over queued behind the sampling runs meanwhile."""
        slot, sampled, span = admission
        try:
            slot.next_token, slot.pending_lp = self._read_first_token(
                sampled)
            with self._span("engine.emit"):
                self._emit(slot, slot.next_token)
        finally:
            span.__exit__(None, None, None)

    def _emit(self, slot: _Slot, token: int) -> None:
        request = slot.request
        if request.done:
            # cancelled from another thread mid-step: discard the
            # token and release the slot
            self._note_discarded()
            slot.request = None
            self._state_stale = True  # graftlint: disable=GL001  # stepper-thread-only
            return
        request.output_ids.append(token)
        self.total_generated += 1
        if len(request.output_ids) == 1 and request.t_submit is not None:
            request.t_first_token = now = time.perf_counter()
            self._mbuf.observe(ENGINE_TTFT,
                               max(0.0, now - request.t_submit))
            if request.t_admit is not None:
                self.record_stage("prefill", now - request.t_admit)
        if request.logprobs is not None and slot.pending_lp is not None:
            chosen, top_vals, top_ids = slot.pending_lp
            k = min(request.logprobs, len(top_ids))
            request.logprob_data.append({
                "id": token, "logprob": float(chosen),
                "top": [(int(top_ids[i]), float(top_vals[i]))
                        for i in range(k)]})
        slot.pending_lp = None
        if (request.presence_penalty or request.frequency_penalty) \
                and not request.done:
            slot.bias_stale = True
        grammar_done = False
        if request.guided is not None and token not in request.stop_ids:
            state = request.guided.advance(request.guided_state, token)
            request.guided_state = state
            # dead state is unreachable while masks are enforced (the
            # sampler can't pick a -1e9 token); treat it as completion
            # defensively rather than decoding garbage forever
            grammar_done = (state is None
                            or request.guided.is_exhausted(state))
            if not grammar_done:
                slot.bias_stale = True
        if token in request.stop_ids or grammar_done:
            request.finish("stop")
        elif len(request.output_ids) >= request.max_tokens:
            request.finish("length")
        elif slot.pos >= self._pos_limit:
            request.finish("length")
        request.push_stream(token)
        if request.done:
            request.push_stream(None)
            slot.request = None
            self._state_stale = True  # graftlint: disable=GL001  # stepper-thread-only

    def _gather_batch(self, active, pos_fill: int = 0):
        """Host-side per-slot input arrays for the jitted decode
        programs — ONE copy shared by the dense, multi-step, and
        speculative paths so a new per-request field cannot desync
        them. ``pos_fill`` is where idle slots park their writes."""
        n = self.config.max_batch
        with self._span("engine.gather"):
            tokens = np.zeros(n, dtype=np.int32)
            pos = np.full(n, pos_fill, dtype=np.int32)
            temp = np.zeros(n, dtype=np.float32)
            topk = np.zeros(n, dtype=np.int32)
            lora_idx = np.zeros(n, dtype=np.int32)
            paths = set()
            for slot in active:
                request = slot.request
                tokens[slot.index] = slot.next_token
                pos[slot.index] = slot.pos
                temp[slot.index] = request.temperature
                topk[slot.index] = request.top_k
                lora_idx[slot.index] = self._adapter_index(request)
                if request.temperature > 0.0:
                    paths.add("topk" if request.top_k > 0 else "full")
            # stepper-thread-only
            self._sampler_paths = tuple(paths) or ("greedy",)  # graftlint: disable=GL001
        return tokens, pos, temp, topk, lora_idx

    def _note_sampler_step(self) -> None:
        """Count the program being launched under each branch of the
        sampler that the slots last gathered engage (the dense step's
        state holds them until a slot changes hands)."""
        for path in self._sampler_paths:
            self.sampler_steps[path] += 1  # graftlint: disable=GL001  # stepper-thread-only
            self._mbuf.inc(ENGINE_SAMPLER_STEPS, 1.0, {"path": path})

    def _gather_state(self, active) -> np.ndarray:
        """The dense step's packed state ([7, B] int32, rows
        _TOKEN.._STEP) as the slots and the sampler's counter have it:
        what ``decode`` is given after a slot changed hands, and what
        it hands on otherwise."""
        tokens, pos, temp, topk, lora_idx = self._gather_batch(
            active, pos_fill=self._dense_park)
        live = np.zeros_like(tokens)
        live[[slot.index for slot in active]] = 1
        return np.stack([tokens, pos, temp.view(np.int32), topk,
                         lora_idx, live,
                         np.full_like(tokens, self._step_counter)])

    def _spec_step(self, active) -> int:
        """One speculation round: G-1 batched draft decodes + ONE
        target verify over the [B, G] chunk; each greedy slot emits
        its accepted draft prefix plus the target's correction (1..G
        tokens per round, every one of them exactly what greedy
        target-only decoding would have produced)."""
        jnp = self._jnp
        G = self.config.spec_tokens
        park = self.config.max_seq - G  # scratch rows for idle slots
        tokens, pos, temp, topk, _lora = self._gather_batch(
            active, pos_fill=park)
        tokens_j, pos_j, temp_j, topk_j = self._upload(
            tokens, pos, temp, topk)
        # stepper-thread-only; this program advances positions the
        # dense step's device state does not see
        self._step_counter += 1  # graftlint: disable=GL001
        self._state_stale = True  # graftlint: disable=GL001
        self._note_sampler_step()
        with self._span("engine.launch"):
            # draft proposals d_1..d_{G-1}: one fused dispatch
            drafts_dev, self.draft_cache_k, self.draft_cache_v = \
                self._draft_propose(self.draft_params, self.draft_cache_k,
                                    self.draft_cache_v, tokens_j, pos_j)
            # one target forward scores the whole chunk
            chunk = jnp.concatenate(
                [tokens_j[:, None], drafts_dev.T], axis=1)   # [B, G]
            greedy, first_sampled, *self.cache = \
                self._verify(self.params, *self.cache,
                             chunk, pos_j, temp_j, topk_j,
                             self._base_key, self._step_counter,
                             self._bias)
        del tokens_j, pos_j, temp_j, topk_j, chunk       # see _upload
        # greedy [B, G], first_sampled [B], drafts [G-1, B]
        greedy, first_sampled, drafts_np = self._readback(
            greedy, first_sampled, drafts_dev)
        drafts_np = drafts_np.T                              # [B, G-1]

        with self._span("engine.emit"):
            for slot in active:
                b = slot.index
                if slot.request.temperature > 0.0:
                    # sampled request: one properly-sampled token from
                    # the target's first-position logits (no
                    # speculation)
                    emitted = [int(first_sampled[b])]
                else:
                    m = 0  # accepted draft tokens
                    while m < G - 1 and drafts_np[b, m] == greedy[b, m]:
                        m += 1
                    emitted = [int(greedy[b, i]) for i in range(m + 1)]
                for token in emitted:
                    slot.pos += 1
                    slot.next_token = token
                    self._emit(slot, token)
                    if slot.request is None:  # finished mid-chunk
                        break
        return len(active)

    def _multi_step(self, active, K: int) -> int:
        """K fused decode iterations in one dispatch; per-slot tokens
        past a stop/max_tokens finish are discarded host-side, so
        outputs match single-step decoding exactly."""
        tokens, pos, temp, topk, lora_idx = self._gather_batch(
            active, pos_fill=self.config.max_seq - K)
        # stepper-thread-only; this program advances positions the
        # dense step's device state does not see
        self._step_counter += 1  # graftlint: disable=GL001
        self._state_stale = True  # graftlint: disable=GL001
        tokens_j, pos_j, temp_j, topk_j, lora_j = self._upload(
            tokens, pos, temp, topk, lora_idx)
        self._note_sampler_step()
        with self._span("engine.launch"):
            toks, *self.cache = self._decode_multi(
                self.params, *self.cache,
                tokens_j, pos_j, temp_j, topk_j,
                self._base_key, self._step_counter,
                self.lora_bank, lora_j, self._bias)
        del tokens_j, pos_j, temp_j, topk_j, lora_j      # see _upload
        (toks,) = self._readback(toks)                   # [K, B]
        with self._span("engine.emit"):
            for slot in active:
                for k in range(K):
                    slot.pos += 1
                    slot.next_token = int(toks[k, slot.index])
                    self._emit(slot, slot.next_token)
                    if slot.request is None:  # finished mid-chunk:
                        break             # later tokens are discarded
        return len(active)

    def _prefill_chunk_step(self, prefilling, decoding) -> None:
        """ONE batched llama_verify_step dispatch advances every
        prefilling slot by a chunk AND decodes every (non-LoRA)
        decoding slot by one token — a decode is just a 1-token chunk
        (vLLM's mixed prefill/decode batches). Fusing them matters:
        separate chunk + decode dispatches doubled the inter-token gap
        on dispatch-bound links, making chunked prefill slower than
        the blocking admission it replaces."""
        C = self.config.chunked_prefill_tokens
        n = self.config.max_batch
        park = self.config.max_seq - C  # scratch rows for idle slots
        # sampling fields come from the shared gather (one copy across
        # all paths); the chunk overlays its own tokens/positions
        tokens, pos, temp, topk, _lora = self._gather_batch(
            prefilling + decoding, pos_fill=park)
        chunk = np.zeros((n, C), dtype=np.int32)
        chunk[:, 0] = tokens  # decoding slots: 1-token "chunk"
        last_idx = np.zeros(n, dtype=np.int32)
        for slot in prefilling:
            ids, p = slot.prefill_ids, slot.prefill_pos
            part = ids[p: p + C]
            row = np.zeros(C, dtype=np.int32)
            row[: len(part)] = part
            chunk[slot.index] = row
            pos[slot.index] = p
            last_idx[slot.index] = len(part) - 1
        # stepper-thread-only; this program advances positions the
        # dense step's device state does not see
        self._step_counter += 1  # graftlint: disable=GL001
        self._state_stale = True  # graftlint: disable=GL001
        chunk_j, pos_j, last_j, temp_j, topk_j = self._upload(
            chunk, pos, last_idx, temp, topk)
        self._note_sampler_step()
        with self._span("engine.launch"):
            tok, *self.cache = self._chunk_prefill(
                self.params, *self.cache,
                chunk_j, pos_j, last_j, temp_j, topk_j,
                self._base_key, self._step_counter, self._bias)
        del chunk_j, pos_j, last_j, temp_j, topk_j       # see _upload
        (tok,) = self._readback(tok)
        with self._span("engine.emit"):
            for slot in prefilling:
                remaining = len(slot.prefill_ids) - slot.prefill_pos
                slot.prefill_pos += min(C, remaining)
                if slot.prefill_pos >= len(slot.prefill_ids):
                    slot.prefilling = False
                    slot.pos = len(slot.prefill_ids)
                    slot.prefill_ids = None
                    slot.next_token = int(tok[slot.index])
                    self._emit(slot, slot.next_token)
            for slot in decoding:
                slot.pos += 1
                slot.next_token = int(tok[slot.index])
                self._emit(slot, slot.next_token)

    def step(self) -> int:
        """Admit + one whole-batch decode step (sampling fused on
        device — only [B] token ids come back). Returns #active slots.

        Instrumented wrapper: step wall time, host and upload time
        (phase-tagged prefill vs decode; all three are what the
        stepper's account grew by across the engine.step span) and
        tokens accumulate in the local buffer, which its own thread
        flushes: nothing here reaches the control plane."""
        account = self._account
        account.bind()
        tokens_before = self.total_generated
        self._admitted_last_step = 0
        self._steps += 1  # graftlint: disable=GL001  # stepper-thread-only
        with self._span("engine.step") as span:
            t0 = account.t
            blocked = account.wall[_BLOCKED]
            upload = account.wall[_UPLOAD]
            handled = self._step_impl()
            emitted = self.total_generated - tokens_before
            phase = ("prefill" if self._admitted_last_step
                     or any(s.request is not None and s.prefilling
                            for s in self.slots)
                     else "decode")
            span.note(phase=phase, slots=handled, tokens=emitted)
        dt = account.t - t0
        self._mbuf.note_step(
            phase, dt, max(0.0, dt - (account.wall[_BLOCKED] - blocked)),
            account.wall[_UPLOAD] - upload, emitted)
        return handled

    def record_stage(self, stage: str, seconds: float) -> None:
        """One stage of one request: the engine's own "queue" and
        "prefill", and what the serving layer saw pass before
        add_request ("dispatch", "prepare"), all into one buffer."""
        self._mbuf.observe(ENGINE_STAGE_SECONDS, max(0.0, seconds),
                           {"stage": stage})

    def flush_metrics(self) -> None:
        """Send the buffered metrics now, on the caller's thread."""
        self._mbuf.flush(force=True)

    def close(self) -> None:
        """Flush once more and end the buffer's flush thread (a
        collected engine ends it too)."""
        self._mbuf.close()

    def _step_impl(self) -> int:
        if self._flight is not None:
            # a dense step is on the device, launched ahead by the call
            # before: admission and the next launch go behind it
            return self._fly(self._flight)
        self._admit()
        # guided slots: re-sync device bias rows with automaton states
        # advanced by the previous step's emissions (one [V] row upload
        # per advanced guided slot — masks memoize per state)
        for s in self.slots:
            if s.request is not None and s.bias_stale:
                self._install_bias(s.request, s.index)
                s.bias_stale = False
        handled = 0
        if self.config.chunked_prefill_tokens > 0:
            prefilling = [s for s in self.slots
                          if s.request is not None and s.prefilling]
            if prefilling:
                # fused mixed batch: prefill chunks + 1-token decodes
                # in one dispatch (LoRA decodes lack a chunk-program
                # path and fall through to the dense step below)
                fused_decodes = [
                    s for s in self.slots
                    if s.request is not None and not s.prefilling
                    and s.request.adapter is None
                    and s.request.logprobs is None]
                self._prefill_chunk_step(prefilling, fused_decodes)
                handled = len(prefilling) + len(fused_decodes)
                active = [s for s in self.slots
                          if s.request is not None and not s.prefilling
                          and (s.request.adapter is not None
                               or s.request.logprobs is not None)]
                if not active:
                    return handled
                # fall through: adapter decodes take the dense step
            else:
                active = [s for s in self.slots
                          if s.request is not None]
                if not active:
                    return 0
        else:
            active = [s for s in self.slots if s.request is not None]
            if not active:
                return 0
        if self._spec and \
                any(s.request.temperature <= 0.0 for s in active) and \
                all(s.request.adapter is None for s in active) and \
                not any(self._has_dynamic_bias(s.request)
                        or s.request.logprobs is not None
                        for s in active) and \
                all(s.draft_ready for s in active) and \
                all(s.pos + self.config.spec_tokens
                    <= self.config.max_seq - 1 for s in active):
            # (all-sampled batches skip speculation: a round would pay
            # the draft scan + G-wide verify to emit 1 token/slot)
            return self._spec_step(active)
        K = self.config.multi_step
        if K > 1 and all(s.pos + K <= self.config.max_seq - 1
                         for s in active) and \
                not any(self._has_dynamic_bias(s.request)
                        or s.request.logprobs is not None
                        for s in active):
            # guided/penalized slots need a bias refresh between
            # tokens, which a fused K-step scan cannot do — dense
            # fallback while any such request is active
            return self._multi_step(active, K) + handled
        # the dense step: nothing of it is in flight, so it is launched
        # as it always was, the state gathered if a slot changed hands
        # stepper-thread-only: the RNG counter and the state's fields
        self._step_counter += 1  # graftlint: disable=GL001
        want_lp = any(s.request.logprobs is not None for s in active)
        state = self._state
        live = tuple(s.index for s in active)
        if self._state_stale or live != self._state_slots:
            # a slot changed hands: the slots' own record of tokens and
            # positions (kept up by the emit loop) is the truth
            (state,) = self._upload(self._gather_state(active))
            self._state_stale = False  # graftlint: disable=GL001
            self._state_slots = live  # graftlint: disable=GL001
            self.state_uploads += 1  # graftlint: disable=GL001
            self._mbuf.inc(ENGINE_STATE_UPLOADS)
        flight = self._launch_decode(
            [(s, s.request) for s in active], [s.pos for s in active],
            state, want_lp=want_lp)
        if self._edits_cold:
            self._warm_edits()
        return handled + self._fly(flight)

    def _warm_edits(self) -> None:
        """Compile ``_park`` and ``_seat`` behind the engine's first
        dense step, on its state and the last admission's first token,
        and drop what they return: a loop's warm-up may never park a
        slot (its requests end in one step), and neither may compile
        under traffic."""
        token = self._warm_token
        if token is None:       # only adopted prefills so far
            return
        with self._span("engine.launch"):
            self._park(self._state,
                       np.zeros(self.config.max_batch, np.int32))
            self._seat(self._state, token, np.zeros(6, np.int32))
        self._edits_cold, self._warm_token = False, None  # graftlint: disable=GL001  # stepper-thread-only

    # -- the dense step's order -----------------------------------------
    # With a dense step in flight the stepper launches the next one from
    # the state and the cache that step returns (device arrays, resolved
    # in order by the runtime) BEFORE it reads the step back, so that the
    # read-back, the emit loop and the launch itself run beside the
    # device. Three things make that safe. (1) A slot changes hands on
    # the device: an ending the host knows before the token comes (by
    # length, by ``_pos_limit``, a cancel already seen) parks the slot
    # in the state the next step is given (``_park``); an admission
    # writes its column, first token included, from device arrays
    # (``_seat``). (2) An ending the host learns from a token (a stop
    # id, a cancel from another thread) is found one step late: the
    # step ahead ran the slot live, its token is discarded in ``_land``
    # (never appended, streamed or counted), its cache row lies inside
    # the slot's own rows (a slot is live only below ``_pos_limit``),
    # which the next ``insert`` overwrites whole, and the slot is
    # parked, or seated anew, before the step after. (3) An arrival gets
    # in front: the step ahead is launched late (``_hold``), and a
    # request that arrives before then is admitted behind the ONE step
    # that runs. ``_may_launch_ahead`` decides, per step, from the
    # slots: where it says no, the step in flight is landed with none
    # behind it and the next call is in today's order.

    @classmethod
    def _needs_order(cls, request: GenerationRequest) -> bool:
        """The host has to see this request's token before the next
        step is launched: its bias row follows what it generated, or
        the token's logprobs are read with it."""
        return (cls._has_dynamic_bias(request)
                or request.logprobs is not None)

    def _may_launch_ahead(self, requests) -> bool:
        """Whether the step after the one in flight may be launched
        before that one is read. ``requests``: those it would run, or
        more. No: the other step programs' engines
        (their rows and counters are the host's), a live request that
        ``_needs_order``, an adopted prefill or such a request at the
        head of the queue with a slot to take (both are admitted in
        today's order, once nothing is in flight)."""
        config = self.config
        if self._spec or config.multi_step > 1 \
                or config.chunked_prefill_tokens > 0:
            return False
        if any(self._needs_order(request) for request in requests):
            return False
        with self._lock:
            if self._prefilled_waiting:
                return False
            return not (self.waiting and self._needs_order(self.waiting[0])
                        and self._free_slots())

    def _fly(self, flight: _Flight) -> int:
        """With ``flight`` on the device: hold for an arrival; nobody
        arriving, launch the next step ahead and only then read
        ``flight`` back and emit it. An arrival is admitted behind
        ``flight`` at once if the host's part of an admission fits
        before ``flight`` ends, so that its tokens are read when they
        come; else they are read first. From there the admission goes
        on as in order (two deep, each first token read as it comes,
        then a look for who arrived meanwhile: a prompt that arrives
        during a prefill goes right behind it, not behind a decode
        step), and the next step is launched last, from the state on
        the device. Returns the slots ``flight`` handled."""
        account = self._account
        following = None
        self._flight = None  # graftlint: disable=GL001  # stepper-thread-only
        if not self._may_launch_ahead([r for _, r in flight.rows]):
            self._land(flight)
            return len(flight.rows)
        arrival = self._hold(flight)
        now = account.clock()
        if arrival:
            fits = (now + max(self._admit_cost_s, default=0.0)
                    <= self._expected_end(flight, now) - _HOLD_SLACK_S)
            queued = collections.deque(self._admit_behind() if fits else ())
            self._land(flight)
            while True:
                if len(queued) < 2:
                    queued.extend(self._admit_behind(bool(queued)))
                if not queued:
                    break
                self._first_token_out(queued.popleft())
        rows = self._rows_after(None if arrival else flight)
        if rows and self._may_launch_ahead([r for _, r, _ in rows]):
            following = self._launch_ahead(rows, ahead=not arrival)
            if not arrival:
                self._launch_lead_s.append(  # graftlint: disable=GL001  # stepper-thread-only
                    following.t_launched - now)
        if not arrival:
            self._land(flight)
        self._flight = following  # graftlint: disable=GL001
        return len(flight.rows)

    def _expected_end(self, flight: _Flight, now: float) -> float:
        """When the device should have finished ``flight``: its start
        on it (the later of the launch and the last read-back's return)
        plus the shortest of the last steps' device times; ``now``
        before any step has been timed."""
        samples = self._step_device_s
        if not samples:
            return now
        return max(self._device_free_at, flight.t_launched) + min(samples)

    def _arrival_waits(self) -> bool:
        """Something the stepper would act on at once: an adopted
        prefill, or a request with a slot to take."""
        with self._lock:
            return bool(self._prefilled_waiting) or (
                bool(self.waiting) and bool(self._free_slots()))

    def _hold(self, flight: _Flight) -> bool:
        """Wait for an arrival, not for the device, until the last
        moment that still hides the host's work: the expected end of
        the step in flight less the host's own lead (the longest of its
        last few times from the end of a hold to the return of the
        launch behind it) and ``_HOLD_SLACK_S``. The stepper waits here
        because the device is busy, so the time is ``blocked``, under a
        span of its own. No hold before a few steps have been timed.
        Returns whether an arrival waits."""
        leads = self._launch_lead_s
        if len(self._step_device_s) < 3 or not leads:
            return self._arrival_waits()
        clock = self._account.clock
        deadline = (self._expected_end(flight, 0.0) - max(leads)
                    - _HOLD_SLACK_S)
        if self._arrival_waits():
            return True
        if clock() >= deadline:
            return False
        with self._span("engine.hold"):
            while True:
                self._arrived.clear()  # graftlint: disable=GL001  # an Event: its own lock
                if self._arrival_waits():
                    return True
                left = deadline - clock()
                if left <= 0:
                    return False
                self._arrived.wait(left)

    def _admit_behind(self, overlapped: bool = False) -> List[_Admission]:
        """Admit the next waiting prompt, if it has a slot, behind what
        the device runs: everything ``_prefill_into`` queues, then the
        slot's column of the state (``_seat``), and no read: the caller
        reads the first token. None admitted where nobody waits, no
        slot is free, or the head of the queue ``_needs_order``."""
        began = self._account.clock()
        with self._lock:
            free = self._free_slots() if self.waiting else None
            if not free or self._needs_order(self.waiting[0]):
                return []
            request = self.waiting.pop(0)
            slot = free[0]
            slot.request = request
        self._admitted_last_step += 1  # graftlint: disable=GL001  # stepper-thread-only
        self._note_admitted(request)
        admission = self._prefill_into(slot, request, overlapped)
        if request.max_tokens > 1 and slot.pos < self._pos_limit:
            # else it ends at its first token and is never live
            self._seat_slot(slot, request, admission.sampled[0])
        self._admit_cost_s.append(  # graftlint: disable=GL001  # stepper-thread-only
            self._account.clock() - began)
        return [admission]

    def _seat_slot(self, slot: _Slot, request: GenerationRequest,
                   token) -> None:
        """``request``'s column into the state on the device, live from
        the next step on, with ``token`` (a device array) to feed."""
        row = np.array(
            [slot.index, slot.pos,
             np.float32(request.temperature).view(np.int32),
             request.top_k, self._adapter_index(request),
             self._step_counter + 1], dtype=np.int32)
        with self._span("engine.launch"):
            self._state = self._seat(self._state, token, row)  # graftlint: disable=GL001  # stepper-thread-only
        if slot.index not in self._state_slots:
            self._state_slots = tuple(sorted(  # graftlint: disable=GL001
                self._state_slots + (slot.index,)))

    def _rows_after(self, flight: Optional[_Flight]) -> list:
        """The (slot, request, position) the next step runs from the
        state on the device, as the host knows them before the tokens
        of ``flight`` come (None: everything is read): every live
        request less those whose next token is their last by length or
        by ``_pos_limit``. Empty also where a live request has no
        column in that state (it is neither in ``flight`` nor live
        there): the step is left to today's order."""
        in_flight = ({} if flight is None else
                     {slot.index: request for slot, request in flight.rows})
        rows = []
        for slot in self.slots:
            request = slot.request
            if request is None or request.done:
                continue
            unread = in_flight.get(slot.index) is request
            if not unread and slot.index not in self._state_slots:
                return []
            pos = slot.pos + unread
            if (len(request.output_ids) + unread >= request.max_tokens
                    or pos >= self._pos_limit):
                continue
            rows.append((slot, request, pos))
        return rows

    def _launch_ahead(self, rows: list, ahead: bool) -> _Flight:
        """Launch the next dense step from the state on the device,
        with the slots that ended parked in it; ``ahead`` of the
        read-back of the step in flight, or with everything read (an
        admission came between)."""
        live = tuple(slot.index for slot, _, _ in rows)
        state = self._state
        gone = [i for i in self._state_slots if i not in live]
        if gone:
            parked = np.zeros(self.config.max_batch, np.int32)
            parked[gone] = 1
            with self._span("engine.launch"):
                state = self._park(state, parked)
        self._state_slots = live  # graftlint: disable=GL001  # stepper-thread-only
        self._state_stale = False  # graftlint: disable=GL001
        self._step_counter += 1  # graftlint: disable=GL001
        return self._launch_decode(
            [(slot, request) for slot, request, _ in rows],
            [pos for _, _, pos in rows], state, ahead=ahead)

    def _launch_decode(self, rows: list, positions: List[int], state,
                       ahead: bool = False,
                       want_lp: bool = False) -> _Flight:
        """Put one dense decode program on the device's queue; what the
        host keeps of it (counters, the account of cache rows) is done
        behind the dispatch, while the device runs."""
        self.decode_steps += 1  # graftlint: disable=GL001  # stepper-thread-only
        with self._span("engine.launch", decode=self.decode_steps):
            (self._state, chosen_lp, top_vals, top_ids,
             self._expert_counts, *self.cache) = self._call_program(
                "decode_lp" if want_lp else "decode", self._decode,
                self.params, self.cache, state,
                self._base_key, self.lora_bank, self._bias,
                self._expert_counts, want_lp=want_lp)
            if self._spec:
                # keep the draft cache in lockstep through dense
                # rounds, or the next _spec_step would condition on KV
                # gaps
                self.draft_cache_k, self.draft_cache_v = \
                    self._draft_sync(
                        self.draft_params, self.draft_cache_k,
                        self.draft_cache_v, state)
        t_launched = self._account.t
        # the state this step was given, dropped while the device runs
        # (see _upload)
        del state
        order = "ahead" if ahead else "in_order"
        self.decode_launches[order] += 1  # graftlint: disable=GL001
        self._mbuf.inc(ENGINE_DECODE_LAUNCHES, 1.0, {"order": order})
        paths = {"topk" if request.top_k > 0 else "full"
                 for _, request in rows if request.temperature > 0.0}
        self._sampler_paths = tuple(paths) or ("greedy",)  # graftlint: disable=GL001
        self._note_sampler_step()
        self._note_kv_rows(positions)
        return _Flight(tuple(rows), self._state,
                       (chosen_lp, top_vals, top_ids) if want_lp else None,
                       t_launched)

    def _land(self, flight: _Flight) -> None:
        """Read a dense step back and emit its tokens, row by row as it
        was launched. A row whose request has ended since (the step was
        launched ahead of the token that ended it) is discarded."""
        account, samples = self._account, self._step_device_s
        free_before, waited = self._device_free_at, account.wall[_BLOCKED]
        (sampled,) = self._readback(flight.state)
        if account.wall[_BLOCKED] - waited > _READ_WAITED_S or not samples:
            # the read waited for the step's end, so that end was seen
            # when it came: the step's device time
            samples.append(self._device_free_at
                           - max(free_before, flight.t_launched))
        else:
            # the step had ended before the read came (the hold was too
            # long, or the host slower than the device): when, nobody
            # saw. Reckon with a shorter step until a read waits again
            samples.append(0.9 * min(samples))
        sampled = sampled[_TOKEN]
        if flight.logprobs is not None:
            # only logprob requests pay the extra device-to-host syncs
            chosen_lp, top_vals, top_ids = self._readback(*flight.logprobs)
            for slot, request in flight.rows:
                if request.logprobs is not None:
                    slot.pending_lp = (chosen_lp[slot.index],
                                       top_vals[slot.index],
                                       top_ids[slot.index])
        with self._span("engine.emit"):
            for slot, request in flight.rows:
                if slot.request is not request:
                    self._note_discarded()
                    continue
                slot.pos += 1
                slot.next_token = int(sampled[slot.index])
                self._emit(slot, slot.next_token)

    def _note_discarded(self) -> None:
        self.discarded_tokens += 1  # graftlint: disable=GL001  # stepper-thread-only
        self._mbuf.inc(ENGINE_DISCARDED_TOKENS)

    # ------------------------------------------------------------------
    def generate(self, prompts_ids: List[List[int]], *,
                 max_tokens: int = 32, temperature: float = 0.0,
                 top_k: int = 0, stop_ids: tuple = ()) -> List[List[int]]:
        """Synchronous batch API: token ids in, token ids out."""
        requests = [
            self.add_request(GenerationRequest(
                prompt_ids=ids, max_tokens=max_tokens,
                temperature=temperature, top_k=top_k, stop_ids=stop_ids))
            for ids in prompts_ids]
        # the loop IS the stepper: each turn runs a step, nothing is polled
        while any(not r.done for r in requests):  # graftlint: disable=GL003
            if self.step() == 0 and any(not r.done for r in requests):
                # nothing active yet (all waiting on slots) — admit again
                time.sleep(0)
        return [r.output_ids for r in requests]

    def fail_all(self, message: str) -> None:
        """Abort every waiting and active request with an error and
        reset the KV caches (used by serving loops when a step raises —
        requests must not hang). The cache reset matters: a failed
        decode/insert may have consumed its donated buffers, leaving
        self.cache_k/v deleted; without fresh caches every later step
        would fail too."""
        with self._lock:
            pending = list(self.waiting)
            self.waiting.clear()
            pending += [entry[0] for entry in self._prefilled_waiting]
            self._prefilled_waiting.clear()
        for request in pending:
            request.finish("error", error=message)
            request.push_stream(None)
        for slot in self.slots:
            if slot.request is not None:
                slot.request.finish("error", error=message)
                slot.request.push_stream(None)
            slot.request = None
            slot.pos = 0
            slot.next_token = 0
            slot.draft_ready = True  # caches reset below
            slot.prefilling = False
            slot.prefill_ids = None
            slot.prefill_pos = 0
            slot.bias_stale = False
            slot.pending_lp = None
        self._state = None
        self._state_stale = True
        self._flight = None
        self._account.prefills = 0   # a step that raised left them open
        self.cache = self._fresh_cache(self.config.model)
        if self._spec:
            self.draft_cache_k, self.draft_cache_v = self._fresh_cache(
                self.config.draft_model)
        if self._prefix_cache is not None:
            # a failed step may have consumed donated buffers that
            # cache entries alias through sharing — drop them all
            with self._lock:
                self._prefix_cache.clear()

    _embed_fn = None  # built lazily on first embed()

    def cancel(self, request: GenerationRequest,
               finish_reason: str = "abort") -> None:
        """Finish a request early from ANY thread (serve stop-string
        hit, client disconnect). Queued requests are withdrawn
        immediately; an active request is marked done and its slot is
        released by the stepper at the request's next emission — no
        cross-thread slot mutation, so no race with a step in flight
        (at most one more token is decoded and discarded)."""
        with self._lock:
            if request.done:
                return
            try:
                self.waiting.remove(request)
            except ValueError:
                pass
            self._prefilled_waiting[:] = [
                e for e in self._prefilled_waiting if e[0] is not request]
            request.finish(finish_reason)
        request.push_stream(None)
        self._arrived.set()

    def embed(self, prompt_ids: List[int]) -> np.ndarray:
        """Mean-pooled final-norm hidden state for a prompt — the
        embedding surface (reference: serve/llm embeddings via vLLM
        embedding models). Pure read of the params; safe to call
        concurrently with the stepper thread."""
        jax, jnp = self._jax, self._jnp
        ids = list(prompt_ids)[-self.config.max_seq:]
        if not ids:
            raise ValueError("cannot embed an empty prompt")
        if self._embed_fn is None:
            c = self.config.model
            hidden = self._family.hidden

            def emb(params, tokens, n):
                h = hidden(params, tokens, c)               # [1, S, D]
                mask = (jnp.arange(tokens.shape[1])
                        < n)[None, :, None].astype(h.dtype)
                pooled = (jnp.sum(h * mask, axis=1)
                          / jnp.maximum(n, 1).astype(h.dtype))
                return pooled[0].astype(jnp.float32)

            self._embed_fn = jax.jit(emb)
        return np.asarray(self._embed_fn(
            self.params, jnp.asarray(self._pad_bucket(ids)),
            jnp.asarray(len(ids), jnp.int32)))

    def stats(self) -> Dict[str, Any]:
        self._mbuf.flush(force=True)
        # lower each hot program seen since the last call once more to
        # read its kernels (outside the lock: tracing takes seconds at
        # full width)
        with self._lock:
            unread = [(name, sig) for name, sig
                      in self._program_sigs.items()
                      if name not in self._program_kernels]
        kernels = {
            name: jax_backend.pallas_kernels(
                jitted.lower(*args, **static).as_text())
            for name, (jitted, args, static) in unread}
        with self._lock:
            self._program_kernels.update(kernels)
            out = {
                "waiting": len(self.waiting),
                "active": sum(1 for s in self.slots
                              if s.request is not None),
                "prefilling": sum(1 for s in self.slots
                                  if s.request is not None
                                  and s.prefilling),
                "max_batch": self.config.max_batch,
                "total_generated": self.total_generated,
                # dense decode programs launched, and how many of them
                # were sent their per-slot state from the host
                "decode_steps": self.decode_steps,
                "state_uploads": self.state_uploads,
                # dense decode programs by order (ahead: launched while
                # the step before was unread), and the tokens sampled
                # for a row whose request had ended when they were read
                "decode_launches": dict(self.decode_launches),
                "discarded_tokens": self.discarded_tokens,
                # rows of a layer's KV cache those steps' attention
                # covered, and the rest of slots x max_seq
                "decode_kv_rows_read": self.decode_kv_rows["read"],
                "decode_kv_rows_skipped": self.decode_kv_rows["skipped"],
                # decode programs by the sampler's branch their live
                # slots engaged (greedy: none, an arg-max alone)
                "sampler_steps": dict(self.sampler_steps),
                # prompts admitted through a prefill of their own, and
                # how many of them were queued on the device before the
                # host waited for the one before
                "admissions": self.admissions,
                "admissions_overlapped": self.admissions_overlapped,
                # which device served, what it compiled, and whether
                # flash attention stepped aside for any shape
                "device": jax_backend.device_report(),
                "programs": dict(self._program_kernels),
                "flash_fallbacks": list(_attention_op.kernel_fallbacks),
                "scan_fallbacks": list(_scan_op.kernel_fallbacks),
                # positions the prefill programs computed, a prompt's
                # own and what its bucket added; the cache by kind
                "prefill_tokens": dict(self.prefill_tokens),
                "cache_bytes": dict(self.cache_bytes),
                # the stepper's account as the flush above read it and
                # sent it, and when (this process's perf_counter):
                # between two reads ``wall`` grows by the time that passed
                "stepper_seconds": {
                    kind: dict(zip(phases, seconds))
                    for (kind, _, phases), seconds in zip(
                        _STEPPER_FAMILIES, self._mbuf.stepper_seconds)},
                "stepper_read_at": self._mbuf._last_flush,
                # the process's last stall episodes (the stall watch's
                # ring: flight_recorder.StallWatch has the keys)
                "stalls": _flight.stalls(_flight.STALL_STACK_FRAMES),
            }
            if self._state_layers:
                # slots x recurrent layers of the dense decode steps
                # whose state moved and stayed parked
                out["state_slots"] = dict(self.state_slots)
            if self._family.expert_counts:
                # what the expert layers counted on the device, as the
                # flush above read it: the router's picks for live rows
                # by where the expert lives, the held experts of the
                # decode steps' layers by whether a live row used them,
                # and the held picks that the layer did not compute
                # (it has no capacity: 0 unless its sort and its groups
                # disagree)
                n = self._mbuf.expert_totals
                out["expert_picks"] = {"held": n["picks_held"],
                                       "absent": n["picks_absent"]}
                out["expert_slots"] = {"hit": n["slots_hit"],
                                       "idle": n["slots_idle"]}
                out["dropped_rows"] = (n["picks_held"]
                                       - n["picks_computed"])
                # the places the prefills' expert layers walked
                out["expert_pairs_walked"] = n["pairs_walked"]
                if "picks_bias_moved" in n:
                    # the picks a selection bias changed, and the rest
                    out["router_picks"] = {"moved": n["picks_bias_moved"],
                                           "kept": n["picks_bias_kept"]}
            if self._prefix_cache is not None:
                out["prefix_cache_entries"] = len(self._prefix_cache)
                out["prefix_hits"] = self.prefix_hits
                out["prefix_misses"] = self.prefix_misses
            return out
