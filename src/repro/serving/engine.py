"""Batched serving engine: continuous batching over fixed cache slots.

  * ``submit`` queues requests (prompt token lists);
  * ``step`` admits queued requests into free slots (single-lane prefill,
    cache splice) and runs ONE batched ``decode_step`` for all slots —
    the cache carries per-lane positions, so lanes at different depths
    decode together (continuous batching);
  * finished sequences (EOS / max_new_tokens / cache full) free slots.

Static shapes: one compilation for prefill (per prompt length bucket) and
one for decode.  The decode step function is exactly what the decode_32k /
long_500k dry-run cells lower.

Analog offload (opt-in): pass ``offload=`` a ``repro.runtime``
``OffloadScheduler``, ``PlanRouter``, or bare ``OffloadExecutor`` and
attention-adjacent FFT/conv work — e.g. spectral retrieval scoring or conv
feature extraction riding along with generation — can be queued via
:meth:`ServingEngine.submit_aux`.  With a scheduler, the decode step runs
an admission *poll* instead of a forced flush: aux groups may be held open
across decode steps under the scheduler's deadline, so trickle aux traffic
accumulates occupancy across steps instead of crossing the conversion
boundary once per step — continuous batching on both sides of the engine.
With a plain router/executor the engine keeps the legacy behavior
(flush once per decode step), which already coalesces aux calls submitted
by different requests within a step into one boundary crossing (the
paper's §6 lever).  Either way the runtime's telemetry observes real
serving traffic for re-planning.

Tracing (opt-in): with a ``repro.runtime.Tracer`` — passed as ``tracer=``
or, by default, the offload runtime's own, so that engine and executor
spans share one timeline — each step records lexical spans on lane
``engine``, each also a ``repro.<name>`` profiler annotation:

  engine.step      the whole step
    engine.admit   one admitted request: its prefill dispatch
                   (``engine.prefill``), its cache splice
                   (``engine.splice``) and its first-token argmax
    engine.aux     the offload poll or flush
    engine.decode  the dispatch of ``decode_step``
    engine.sample  the dispatch of ``sample_greedy`` (every lane's argmax
                   and its ``last_token`` in one program), the step's one
                   ``device_get`` (tokens, positions) and the host's
                   EOS, length and cache-full checks

plus a retrospective ``queued`` span per request (``submit`` to the start
of its admission) and the counters ``decode_steps``, ``admitted``,
``prefill_tokens`` (left padding included), ``decode_positions`` (the
positions each decode step attended, summed over its live lanes) and
``sampled_lanes`` (the live lanes whose token came from ``sample_greedy``)
in ``tracer.metrics``.  A model with experts also annotates each
``engine.prefill`` and ``engine.decode`` span with the expert layers' load
and counts it: ``expert_tokens`` (the (token, held expert) pairs
computed), ``experts_reached`` (held experts of all expert layers that
got a token) and ``max_expert_tokens`` (the most pairs one held expert
got).  The load comes back in the fetch the step already makes (the
first-token argmax at admission, the tokens and positions after a
decode).  No span adds a host-device sync; without a tracer no span site
calls into the tracer or the profiler.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import program_name
from repro.models.config import ModelConfig
from repro.models.model import LM

__all__ = ["Request", "ServingEngine"]


@program_name("sample_greedy")
def _sample_greedy(logits: jax.Array, last_token: jax.Array,
                   live: jax.Array):
    """Greedy tokens of every lane, ``(B,)`` int32 (first index on ties),
    and the next decode input: the token where the lane is live, its last
    token where it is not."""
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, jnp.where(live[:, None], nxt[:, None], last_token)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Any, *, batch_slots: int = 4,
                 max_len: int = 256, eos_id: int | None = None,
                 prompt_bucket: int = 1, offload: Any | None = None,
                 tracer: Any | None = None) -> None:
        self.cfg = cfg
        self.model = LM(cfg)
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.bucket = prompt_bucket
        self.queue: list[Request] = []
        self.active: dict[int, Request] = {}
        self.cache = self.model.init_cache(batch_slots, max_len)
        self.last_token = jnp.zeros((batch_slots, 1), jnp.int32)
        self.live = [False] * batch_slots
        self._decode = jax.jit(program_name("decode_step")(
            lambda p, c, t: self.model.decode_step(p, c, t)))
        self._sample = jax.jit(_sample_greedy)
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, max_len=max_len))
        # analog-offload hook: an OffloadScheduler / PlanRouter /
        # OffloadExecutor (duck-typed on submit/flush/pending, schedulers
        # additionally on poll) or None; aux submissions batch across
        # decode steps.
        self.offload = offload
        self.tracer = (tracer if tracer is not None
                       else getattr(offload, "tracer", None))
        self._submitted: dict[int, float] = {}   # id(request) -> submit time

    # -- client API ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        if self.tracer is not None:
            self._submitted[id(req)] = self.tracer.now()
        self.queue.append(req)

    def submit_aux(self, category: str, x: jax.Array, **kwargs):
        """Queue attention-adjacent FFT/conv/matmul work on the offload
        runtime; returns an ``OffloadResult`` handle.  With a plain
        router/executor hook it materializes at the next decode step; with
        an ``OffloadScheduler`` hook it materializes when admission control
        releases its group (full / deadline / futile — possibly several
        decode steps later).  ``handle.get()`` always forces it.  Requires
        the engine to have been constructed with ``offload=``."""
        if self.offload is None:
            raise RuntimeError("engine built without offload= runtime")
        return self.offload.submit(category, x, **kwargs)

    @property
    def pending_aux(self) -> int:
        # the runtime's queue is the single source of truth: callers may
        # drain it directly (handle.get(), router.flush()) between steps
        return self.offload.pending if self.offload is not None else 0

    def flush_aux(self) -> list:
        """Dispatch queued aux work as batched accelerator invocations."""
        return self.offload.flush() if self.offload is not None else []

    def idle(self) -> bool:
        return not self.queue and not self.active and not self.pending_aux

    # -- internals -------------------------------------------------------------
    def _span(self, name: str):
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, lane="engine")

    def _splice_slot(self, slot: int, slot_cache: Any) -> None:
        """Copy a prefilled 1-lane cache into lane ``slot`` of the batch
        cache (every cache leaf's lane dim is the one sized batch_slots
        where the source's is 1)."""
        def put(dst, src):
            if not hasattr(dst, "ndim") or dst.ndim == 0:
                return dst
            for d in range(dst.ndim):
                if dst.shape[d] == self.slots and src.shape[d] == 1:
                    idx = [slice(None)] * dst.ndim
                    idx[d] = slice(slot, slot + 1)
                    return dst.at[tuple(idx)].set(src.astype(dst.dtype))
            return dst
        self.cache = jax.tree_util.tree_map(put, self.cache, slot_cache)

    def _load(self, cache: dict):
        """The expert load a cache carries, where it is to be recorded."""
        return cache.get("expert_tokens") if self.tracer is not None else None

    def _record_load(self, span: Any, load: Any) -> None:
        if load is None:
            return
        got = {"expert_tokens": int(load.sum()),
               "experts_reached": int((load > 0).sum()),
               "max_expert_tokens": int(load.max())}
        span.annotate(**got)
        for name, n in got.items():
            self.tracer.metrics.counter(name).inc(n)

    def _admit(self) -> None:
        tr = self.tracer
        free = [s for s in range(self.slots) if s not in self.active]
        while free and self.queue:
            slot = free.pop(0)
            req = self.queue.pop(0)
            if tr is not None:
                t_sub = self._submitted.pop(id(req), None)
                if t_sub is not None:
                    tr.record("queued", t_sub, tr.now(), lane="engine",
                              kind="async", rid=req.rid)
            with self._span("engine.admit"):
                plen = len(req.prompt)
                # optional left-pad bucketing bounds prefill recompiles;
                # pad tokens occupy real cache slots (prompt_bucket=1 for
                # exact)
                pad = (-plen) % self.bucket
                toks = jnp.asarray([0] * pad + req.prompt,
                                   jnp.int32)[None, :]
                with self._span("engine.prefill") as prefill_span:
                    slot_cache, logits = self._prefill(self.params,
                                                       {"tokens": toks})
                with self._span("engine.splice"):
                    self._splice_slot(slot, slot_cache)
                nxt, load = jax.device_get((jnp.argmax(logits[0]),
                                            self._load(slot_cache)))
                nxt = int(nxt)
                req.out_tokens.append(nxt)
                self.last_token = self.last_token.at[slot, 0].set(nxt)
                self.live[slot] = True
                self.active[slot] = req
            if tr is not None:
                tr.metrics.counter("admitted").inc()
                tr.metrics.counter("prefill_tokens").inc(plen + pad)
                self._record_load(prefill_span, load)

    def step(self) -> list[Request]:
        """Admit waiting requests, run the aux offload admission pass (a
        scheduler poll when one is driving — held groups survive the step;
        a forced flush otherwise), then one batched decode step."""
        with self._span("engine.step"):
            self._admit()
            with self._span("engine.aux"):
                poll = getattr(self.offload, "poll", None)
                if poll is not None:
                    # scheduler-driven: release only full/due/futile
                    # groups; a partially filled group rides to the next
                    # decode step
                    poll()
                elif self.pending_aux:
                    self.flush_aux()
            if not self.active:
                return []
            with self._span("engine.decode") as decode_span:
                logits, self.cache = self._decode(self.params, self.cache,
                                                  self.last_token)
            finished = []
            with self._span("engine.sample"):
                nxt, self.last_token = self._sample(
                    logits, self.last_token, np.asarray(self.live))
                nxt_host, pos_host, load = jax.device_get(
                    (nxt, self.cache["pos"], self._load(self.cache)))
                lanes = list(self.active.items())
                for slot, req in lanes:
                    tok = int(nxt_host[slot])
                    req.out_tokens.append(tok)
                    if (self.eos_id is not None and tok == self.eos_id) \
                            or len(req.out_tokens) >= req.max_new_tokens \
                            or int(pos_host[slot]) >= self.max_len - 1:
                        req.done = True
                        finished.append(req)
                        del self.active[slot]
                        self.live[slot] = False
            if self.tracer is not None:
                m = self.tracer.metrics
                m.counter("decode_steps").inc()
                m.counter("sampled_lanes").inc(len(lanes))
                m.counter("decode_positions").inc(
                    sum(int(pos_host[slot]) for slot, _ in lanes))
                self._record_load(decode_span, load)
            return finished

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        done: list[Request] = []
        for _ in range(max_steps):
            done.extend(self.step())
            if self.idle():
                break
        return done
