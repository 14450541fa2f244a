"""Driver of the offload cells: an ``OffloadExecutor`` built as its
configuration says, fed by a ``closed_bursts`` mix.

The timed path is ``OffloadExecutor.submit``/``flush``.  A sample of the
bursts, drawn from the seed over the whole window, is kept on the device
and compared with the float64 reference once the window has closed.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmarks.chip import traffic
from benchmarks.chip.common import (BenchError, load_module, metric, np_rng,
                                    percentile, ROOT)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def build_executor(cfg: dict, tracer=None):
    """The ``OffloadExecutor`` an offload configuration file states, after
    checking that the program's accelerator spec is that configuration."""
    from repro.core import accelerator
    from repro.runtime import FidelityChecker, OffloadExecutor

    spec = getattr(accelerator, cfg["spec"])
    stated = (tuple(cfg["aperture"]), cfg["dac_bits"], cfg["adc_bits"])
    if (tuple(spec.slm_pixels), spec.dac.bits, spec.adc.bits) != stated:
        raise BenchError(f"{cfg['spec']} is not the configuration the file "
                         f"states: {stated}")
    kw = dict(cfg["executor"])
    fidelity = FidelityChecker() if kw.pop("fidelity", False) else None
    return OffloadExecutor(spec, fidelity=fidelity, tracer=tracer, **kw)


def reservoir_slot(rng, index: int, keep: int) -> int:
    """Where item ``index`` goes in a reservoir of ``keep`` items (each of
    the items seen so far equally likely to be held); ``keep`` or more
    when it is not kept."""
    return index if index < keep else int(rng.integers(index + 1))


class Session:
    """Set-up on construction; ``measure`` runs the window."""

    def __init__(self, cell: dict, seed: int, devices: list, *,
                 trace: bool = False) -> None:
        import jax
        from repro.runtime import Tracer

        self.cell, self.seed, self.devices = cell, seed, devices
        cfg, mix = cell["config"], cell["mix"]
        self.cfg, self.mix = cfg, mix
        if mix["kind"] != "closed_bursts":
            raise BenchError(f"the offload driver runs no {mix['kind']!r} mix")
        self.bits = (cfg["dac_bits"], cfg["adc_bits"])
        self.ref = load_module(ROOT / cfg["reference"])
        self.tracer = Tracer(capacity=1 << 22) if trace else None
        self.ex = build_executor(cfg, self.tracer)
        self.backend = self.ex.default_backend
        self.category = mix["category"]
        self.shape = tuple(mix.get("frame_shape") or self.ex.spec.slm_pixels)
        self._jax = jax
        self._warm()

    # -- set-up ----------------------------------------------------------------
    def _frames(self, index: int):
        """Burst ``index``: a tuple of fresh device arrays."""
        return traffic.make_frames(self.mix["frames"], self.seed, index,
                                   self.mix["burst"], self.shape)

    def _warm(self) -> None:
        """Compile the shapes the window dispatches, off the record: one
        burst through the real path (dispatch, tiling, shadow)."""
        xs = self._frames(2**31 - 1)          # an index the window never uses
        hs = [self.ex.submit(self.category, x) for x in xs]
        self.ex.flush()
        self._jax.block_until_ready([h.value for h in hs])
        # the window's first burst, made here so that nothing compiles there
        self._first = self._frames(0)
        self._jax.block_until_ready(self._first)
        self.counts0 = self._counts()

    def _counts(self) -> dict:
        st = self.ex.telemetry.stats.get((self.category, self.backend))
        if st is None:
            return {"calls": 0, "invocations": 0}
        return {"calls": st.calls, "invocations": st.invocations}

    # -- the window --------------------------------------------------------------
    def measure(self, seconds: float) -> None:
        """Bursts until ``seconds`` have passed.  Of all the bursts, a
        reservoir drawn from the seed keeps ``sample.bursts`` for the
        comparison, each burst as likely as any other."""
        jax = self._jax
        keep = self.mix["sample"]["bursts"]
        rng = np_rng(self.seed, 5)
        self.latencies: list[float] = []
        self.sampled: list[list] = []           # [(frame, handle), ...]
        self.kept: list[int] = []               # the bursts sampled
        self.served: collections.Counter = collections.Counter()
        self.attempted = 0
        self.w0 = time.perf_counter()
        end = self.w0 + seconds
        b = 0
        nxt = self._first
        while time.perf_counter() < end:
            xs = nxt
            with _annotate("bench.submit"):
                t_sub, hs = [], []
                for x in xs:
                    t_sub.append(time.perf_counter())
                    hs.append(self.ex.submit(self.category, x))
            with _annotate("bench.flush"):
                self.ex.flush()
            with _annotate("bench.make_frames"):
                nxt = self._frames(b + 1)
            with _annotate("bench.wait"):
                jax.block_until_ready([h.value for h in hs])
            t_done = time.perf_counter()
            self.latencies.extend(t_done - t for t in t_sub)
            self.served.update(h.backend for h in hs)
            self.attempted += len(hs)
            slot = reservoir_slot(rng, b, keep)
            if slot < len(self.sampled):
                self.sampled[slot] = list(zip(xs, hs))
                self.kept[slot] = b
            elif slot < keep:
                self.sampled.append(list(zip(xs, hs)))
                self.kept.append(b)
            b += 1
        self.w1 = time.perf_counter()
        self.bursts = b
        self.counts1 = self._counts()

    # -- results -------------------------------------------------------------------
    def report_lines(self) -> list[str]:
        c0, c1 = self.counts0, self.counts1
        return [f"window {self.w1 - self.w0:.6f} s, {self.attempted} frames "
                f"attempted in {self.bursts} bursts, "
                f"{c1['invocations'] - c0['invocations']} dispatches, "
                f"served by {dict(self.served)}; bursts compared: "
                f"{sorted(self.kept)}"]

    def end_to_end(self) -> dict:
        window = self.w1 - self.w0
        return {"frames_per_s": metric(len(self.latencies) / window,
                                       "frames/s"),
                "frame_p95_ms": metric(percentile(self.latencies, 95) * 1e3,
                                       "ms")}

    def failed(self) -> int:
        """Frames served by another backend than the configured one (a
        fallback)."""
        return sum(k for b, k in self.served.items() if b != self.backend)

    def layer_context(self) -> dict:
        c0, c1 = self.counts0, self.counts1
        spans = []
        if self.tracer is not None:
            spans = [s for s in self.tracer.spans()
                     if s.t1 is not None and s.t0 >= self.w0
                     and s.t1 <= self.w1]
        return {"window_s": self.w1 - self.w0,
                "frames": len(self.latencies),
                "calls": c1["calls"] - c0["calls"],
                "invocations": c1["invocations"] - c0["invocations"],
                "spans": spans, "category": self.category,
                "frame_shape": self.shape, "chips": len(self.devices)}

    def release(self) -> None:
        """Free the program's state before the reference runs, keeping
        the sampled answers."""
        self.ex.close()
        self.sampled = [(np.asarray(x), np.asarray(h.value))
                        for burst in self.sampled for x, h in burst]
        del self.ex

    def readings(self, control: bool = False) -> dict:
        """The numbers compared, of the sampled answers against the
        float64 reference; with ``control``, of the reference at the
        configuration's control precision put in the program's place."""
        if not self.sampled:
            raise BenchError("the window kept no sample to compare")
        ref, bits = self.ref, self.bits
        ctl = tuple(self.cfg["control"][k] for k in ("dac_bits", "adc_bits"))

        def gap(x, y):
            want = ref.fft_reference(x, bits)
            got = ref.fft_reference(x, ctl) if control else y
            return ref.adc_code_gap(got, want, bits[1])
        return {"fft_code_gap": max(gap(x, y) for x, y in self.sampled)}

    def checks(self, limits: dict) -> list[dict]:
        """Each number compared, beside its limit."""
        return [{"name": n, "value": v, "limit": limits[n],
                 "frames": len(self.sampled)}
                for n, v in self.readings().items()]
