"""Driver of the serving cells: ``ServingEngine`` over the model the
configuration file states, with weights drawn on the device from the
seed, its ``offload=`` hook an executor built as the offload
configuration it names, fed by a ``closed_chat`` mix.

The timed path is ``ServingEngine.submit``/``step``/``submit_aux``.  Once
the window has closed, a sample of the requests it finished (drawn from
the seed, the longest among them) is run through the plain reference
over prompt and served tokens, and the widest gap by which a served
token's logit lies below the reference's best is compared with its
limit.  The aux fft answers of the sampled requests are compared with
the float64 optics reference.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from benchmarks.chip import traffic
from benchmarks.chip.common import (BenchError, ROOT, jax_key, load_json,
                                    load_module, metric, np_rng, percentile)
from benchmarks.chip.drivers.offload import build_executor


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def model_dims(cfg: dict) -> dict:
    """The model entries of a configuration file, with the padded vocab
    the work counts need."""
    m = dict(cfg["model"])
    pad = m["vocab_pad_multiple"]
    m["padded_vocab"] = -(-m["vocab_size"] // pad) * pad
    return m


class Session:
    """Set-up on construction (weights, engine, warm-up, ramp to a full
    batch); ``measure`` runs the window."""

    def __init__(self, cell: dict, seed: int, devices: list, *,
                 trace: bool = False) -> None:
        import jax
        from repro.models import init_params
        from repro.models.config import ModelConfig
        from repro.runtime import Tracer
        from repro.serving import Request, ServingEngine

        self._jax, self._Request = jax, Request
        self.cell, self.seed, self.devices = cell, seed, devices
        cfg, mix = cell["config"], cell["mix"]
        self.cfg, self.mix = cfg, mix
        if mix["kind"] != "closed_chat":
            raise BenchError(f"the serve driver runs no {mix['kind']!r} mix")
        self.dims = model_dims(cfg)
        self.model = ModelConfig(**cfg["model"])
        self.ref = load_module(ROOT / cfg["reference"])
        off_cfg = load_json(cfg["offload"])
        self.optics = load_module(ROOT / off_cfg["reference"])
        self.bits = (off_cfg["dac_bits"], off_cfg["adc_bits"])
        self.tracer = Tracer(capacity=1 << 22) if trace else None
        self.ex = build_executor(off_cfg, self.tracer)
        self.frame_shape = tuple(mix.get("aux_frame_shape")
                                 or self.ex.spec.slm_pixels)
        self.params = jax.jit(lambda k: init_params(self.model, k))(
            jax_key(seed, 7))
        eng = cfg["engine"]
        self.engine = ServingEngine(self.model, self.params,
                                    batch_slots=eng["batch_slots"],
                                    max_len=eng["max_len"], offload=self.ex)
        self.pool = traffic.chat_requests(mix, seed)
        self.next = 0
        self.sub_t: dict[int, float] = {}
        self.first_t: dict[int, float] = {}
        self.aux: dict[int, object] = {}         # rid -> (frame, handle)
        self.aux_backend: dict[int, str] = {}
        self.aux_kept: dict[int, tuple] = {}     # rid -> (frame, answer)
        self.aux_off = int(np_rng(seed, 8).integers(mix["sample"]["aux_every"]))
        self.reqs: dict[int, object] = {}
        self.done_t: dict[int, float] = {}
        self._warm()
        self._ramp()

    # -- traffic -----------------------------------------------------------------
    def _request(self, rid: int, plen: int, max_new: int):
        toks = traffic.prompt_tokens(self.seed, rid, plen,
                                     self.model.vocab_size)
        return self._Request(rid=rid, prompt=toks, max_new_tokens=max_new)

    def _send(self) -> None:
        """One client sends its next request and its aux frame."""
        rid = self.next
        self.next += 1
        plen, max_new = self.pool[rid % len(self.pool)]
        req = self._request(rid, plen, max_new)
        frame = traffic.make_frames(self.mix["aux_frames"], self.seed, rid,
                                    1, self.frame_shape)[0]
        self.sub_t[rid] = time.perf_counter()
        self.reqs[rid] = req
        self.engine.submit(req)
        self.aux[rid] = (frame, self.engine.submit_aux("fft", frame))

    def _step(self) -> list:
        with _annotate("bench.step"):
            fin = self.engine.step()
        now = time.perf_counter()
        for r in list(self.engine.active.values()) + fin:
            if r.rid not in self.first_t and r.out_tokens:
                self.first_t[r.rid] = now
        for r in fin:
            self.done_t[r.rid] = now
            self._settle_aux(r.rid)
        return fin

    def _settle_aux(self, rid: int) -> None:
        """A finished request's aux answer retired steps ago: note the
        backend that served it and let go of its device arrays, keeping
        frame and answer on the host where the seed's sample takes them.
        (Holding every full-aperture frame of the run on the device
        fragments the memory the decode step reserves.)"""
        frame, h = self.aux.pop(rid)
        h.wait()
        self.aux_backend[rid] = h.backend
        if (rid + self.aux_off) % self.mix["sample"]["aux_every"] == 0:
            self.aux_kept[rid] = (np.asarray(frame), np.asarray(h.value))

    # -- set-up ------------------------------------------------------------------
    def _warm(self) -> None:
        """Compile every prefill length of the pool, the splice into every
        slot, the decode step and the aux fft path, off the record: one
        request of each length per slot, two tokens each."""
        lengths = sorted({p for p, _ in self.pool})
        slots = self.cfg["engine"]["batch_slots"]
        for i in range(max(slots, len(lengths))):
            rid = -(i + 1)                  # ids the window never uses
            req = self._request(2**40 + i, lengths[i % len(lengths)], 2)
            req.rid = rid
            self.engine.submit(req)
            frame = traffic.make_frames(self.mix["aux_frames"], self.seed,
                                        2**31 - 1, 1, self.frame_shape)[0]
            self.engine.submit_aux("fft", frame)
        while not self.engine.idle():
            self.engine.step()
        # a step flushes however many aux frames arrived since the last
        # one: warm each stack depth those groups dispatch
        depths = set()
        for k in range(1, slots + 1):
            t = self.ex.resolve_tile_k("fft", frame, k)
            depths |= {t} | ({k % t} if k % t else set())
        for d in sorted(depths):
            hs = [self.ex.submit("fft", frame) for _ in range(d)]
            self.ex.flush()
            self._jax.block_until_ready([h.value for h in hs])
        self._jax.block_until_ready(self.engine.cache)

    def _ramp(self) -> None:
        """The closed loop until every client has finished one request, so
        that the window opens on a full, staggered batch."""
        clients = self.mix["clients"]
        for _ in range(clients):
            self._send()
        finished = 0
        while finished < clients:
            fin = self._step()
            finished += len(fin)
            for _ in fin:
                self._send()
        self.counts0 = self._counts()

    def _counts(self) -> dict:
        st = self.ex.telemetry.stats.get(("fft", self.ex.default_backend))
        return {"calls": st.calls if st else 0,
                "invocations": st.invocations if st else 0}

    # -- the window --------------------------------------------------------------
    def _tokens(self) -> int:
        return sum(len(r.out_tokens) for r in self.reqs.values())

    def measure(self, seconds: float) -> None:
        self.steps: list[dict] = []
        tok0 = self._tokens()
        self.w0 = time.perf_counter()
        end = self.w0 + seconds
        while time.perf_counter() < end:
            before = {r.rid for r in self.engine.active.values()}
            fin = self._step()
            lanes = list(self.engine.active.values()) + fin
            # per lane: the prompt it prefilled, if admitted in this
            # step, and the positions its decode attended to
            self.steps.append({
                "prefill": [len(r.prompt) for r in lanes
                            if r.rid not in before],
                "context": [len(r.prompt) + len(r.out_tokens) - 1
                            for r in lanes]})
            with _annotate("bench.submit"):
                for _ in fin:
                    self._send()
        self.w1 = time.perf_counter()
        self.tokens = self._tokens() - tok0
        self.admitted = [rid for rid, t in self.first_t.items()
                         if self.w0 <= t <= self.w1]
        self.finished = [rid for rid, t in self.done_t.items()
                         if self.w0 <= t <= self.w1]
        self.counts1 = self._counts()
        self.attempted = len(self.admitted)

    # -- results -----------------------------------------------------------------
    def report_lines(self) -> list[str]:
        n = len(self.steps)
        return [f"window {self.w1 - self.w0:.6f} s, {n} steps, "
                f"{self.tokens} tokens, {len(self.admitted)} requests "
                f"admitted, {len(self.finished)} finished"]

    def end_to_end(self) -> dict:
        window = self.w1 - self.w0
        ttft = [self.first_t[r] - self.sub_t[r] for r in self.admitted]
        return {"tokens_per_s": metric(self.tokens / window, "tokens/s"),
                "ttft_p50_ms": metric(percentile(ttft, 50) * 1e3, "ms")}

    def layer_context(self) -> dict:
        spans = []
        if self.tracer is not None:
            spans = [s for s in self.tracer.spans()
                     if s.t1 is not None and s.t0 >= self.w0
                     and s.t1 <= self.w1]
        return {"window_s": self.w1 - self.w0, "steps": self.steps,
                "model": self.dims, "spans": spans,
                "frames": self.counts1["calls"] - self.counts0["calls"],
                "calls": self.counts1["calls"] - self.counts0["calls"],
                "invocations": (self.counts1["invocations"]
                                - self.counts0["invocations"]),
                "chips": len(self.devices), "category": "serve"}

    def _sample(self) -> list[int]:
        """Finished requests to compare: the longest, then others drawn
        from the seed, until ``sample.tokens`` served tokens."""
        fin = sorted(self.finished)
        if not fin:
            raise BenchError("the window finished no request")
        total = lambda r: len(self.reqs[r].prompt) + len(self.reqs[r].out_tokens)
        longest = max(fin, key=total)
        rest = [r for r in np_rng(self.seed, 6).permutation(fin).tolist()
                if r != longest]
        out, served = [longest], len(self.reqs[longest].out_tokens)
        for r in rest:
            if served >= self.mix["sample"]["tokens"]:
                break
            out.append(r)
            served += len(self.reqs[r].out_tokens)
        return out

    def release(self) -> None:
        """Free the program's state (engine, weights, executor) before the
        reference runs, keeping the sample."""
        self.sample = [(self.reqs[r].prompt, self.reqs[r].out_tokens)
                       for r in self._sample()]
        for rid in [r for r in self.admitted if r in self.aux]:
            self._settle_aux(rid)
        self.aux_sample = [self.aux_kept[r] for r in self.admitted
                           if r in self.aux_kept]
        if not self.aux_sample:
            raise BenchError("the window's sample holds no aux frame")
        self.aux_backends = collections.Counter(
            self.aux_backend[r] for r in self.admitted)
        self.ex.close()
        del self.engine, self.params, self.ex, self.aux
        gc.collect()

    def failed(self) -> int:
        """Aux frames served by another backend than the configured one,
        and served tokens outside the vocabulary."""
        wrong = sum(k for b, k in self.aux_backends.items()
                    if b != "optical-sim")
        bad = sum(1 for p, out in self.sample for t in out
                  if not 0 <= t < self.model.vocab_size)
        return wrong + bad

    def reference_gaps(self, control: str | None = None):
        """The widest gap below the reference's best logit of a served
        token, and with ``control`` that of the token the control's
        precision puts first."""
        return logit_gaps(self.ref, self.dims, self.cfg["engine"]["max_len"],
                          self.seed, self.sample, control)

    def readings(self, control: bool = False) -> dict:
        """The numbers compared: the served tokens' logit gap and the aux
        frames' ADC code gap; with ``control``, those of the reference at
        the configuration's control precision (the logit gap of the token
        it puts first, the code gap of the optics at the control bits)."""
        optics, bits = self.optics, self.bits
        precision = self.cfg["control"]["precision"] if control else None
        served, ctl = self.reference_gaps(precision)
        cbits = tuple(self.cfg["control"][k] for k in ("dac_bits", "adc_bits"))

        def code_gap(x, y):
            got = optics.fft_reference(x, cbits) if control else y
            return optics.adc_code_gap(got, optics.fft_reference(x, bits),
                                       bits[1])
        return {"logit_gap": ctl if control else served,
                "fft_code_gap": max(code_gap(x, y)
                                    for x, y in self.aux_sample)}

    def checks(self, limits: dict) -> list[dict]:
        sizes = {"logit_gap": sum(len(o) for _, o in self.sample),
                 "fft_code_gap": len(self.aux_sample)}
        return [{"name": n, "value": v, "limit": limits[n], "count": sizes[n]}
                for n, v in self.readings().items()]


def logit_gaps(ref, dims: dict, length: int, seed: int, sample,
               control: str | None):
    """Widest gap, over every served position of ``sample`` (a list of
    ``(prompt, served tokens)``), by which the served token's reference
    logit lies below the reference's best; with ``control``, also the
    widest gap of the token the control precision puts first.  Each
    sequence is padded to the cache length, so one program serves all."""
    import jax
    import jax.numpy as jnp

    w = jax.jit(lambda k: ref.init_weights(dims, k))(jax_key(seed, 7))

    @jax.jit
    def gaps(w, toks, pos, served):
        with jax.default_matmul_precision("highest"):
            lg = ref.logits_at(dims, w, toks, pos)
            best = lg.max(axis=-1)
            g_served = best - jnp.take_along_axis(lg, served[:, None], 1)[:, 0]
            if control is None:
                return g_served, g_served
            lc = ref.logits_at(dims, w, toks, pos, control)
            pick = jnp.argmax(lc, axis=-1)
            g_ctl = best - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
            return g_served, g_ctl

    worst, worst_ctl = 0.0, 0.0
    for prompt, out in sample:
        seq = list(prompt) + list(out)
        toks = np.zeros(length, np.int32)
        toks[:len(seq)] = seq[:length]
        n = len(out)
        pos = np.zeros(length, np.int32)
        srv = np.zeros(length, np.int32)
        pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        srv[:n] = out
        g, c = gaps(w, toks, pos, srv)
        worst = max(worst, float(np.max(np.asarray(g)[:n])))
        worst_ctl = max(worst_ctl, float(np.max(np.asarray(c)[:n])))
    del w
    return worst, (worst_ctl if control is not None else None)
