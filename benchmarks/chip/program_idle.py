"""Device idle time named by the program's own spans.

The program's lexical spans (``Tracer.span``) also open profiler
annotations ``repro.<name>``, but the trace reduction (``xplane.py``)
keeps only the benchmark's ``bench.*`` host spans, and a reader gets the
program's spans as ``Tracer`` spans on ``time.perf_counter``.  This puts
those spans on the trace's clock through one anchor: a program span that
opens once at the start of each benchmark host span of one name
(``flush`` in ``bench.flush``, ``engine.step`` in ``bench.step``).  The
offset between the two clocks is the largest that keeps every anchor
from opening before its host span, so it errs by the shortest lag
between the two openings (microseconds), and every anchor must still
close inside its host span.  Where the counts differ or no offset does
that, nothing is read.

Each idle gap of a device is then split by its innermost covering span,
program (``repro.<name>``) or benchmark (``bench.*``), as ``xplane``
splits it by the benchmark's spans alone.
"""

from __future__ import annotations

from benchmarks.chip import xplane


def clock_offset_ns(spans, host_spans, anchor: str, bench: str):
    """Nanoseconds to add to ``anchor`` spans' ``perf_counter`` seconds
    (times 1e9) to put them on the trace's clock, each at the start of
    the ``bench`` host span it ran in, one to one in order; None where no
    offset puts every anchor inside its host span."""
    progs = sorted((s.t0 * 1e9, s.t1 * 1e9) for s in spans
                   if s.name == anchor)
    hosts = sorted((a, b) for a, b, n in host_spans if n == bench)
    if not progs or len(progs) != len(hosts):
        return None
    lo = max(h0 - p0 for (p0, _), (h0, _) in zip(progs, hosts))
    hi = min(h1 - p1 for (_, p1), (_, h1) in zip(progs, hosts))
    return lo if lo <= hi else None


def idle_by_span(ctx: dict, anchor: str, bench: str, top: int = 10):
    """Device idle seconds under each innermost covering span (mean over
    chips) and the longest gaps, by the name of the span that covers most
    of each; None where the program annotated no span or its spans cannot
    be put on the trace's clock."""
    red = ctx["trace"]
    spans = ctx["spans"]
    marked = [s for s in spans if getattr(s, "annotated", False)]
    if not marked:
        return None
    off = clock_offset_ns(spans, red.host_spans, anchor, bench)
    if off is None:
        return None
    prog = [(s.t0 * 1e9 + off, s.t1 * 1e9 + off, f"repro.{s.name}")
            for s in marked]
    host = sorted(list(red.host_spans) + prog)
    busy = [ab for d in red.devices for ab in d.busy]
    lo = min([a for a, _, _ in host] + [a for a, _ in busy])
    hi = max([b for _, b, _ in host] + [b for _, b in busy])
    return xplane._gaps(red.devices, host, lo, hi, top)


def idle_s(ctx: dict, name: str, anchor: str, bench: str):
    """Device idle seconds under the program's innermost ``name`` spans;
    None where the window holds no such span or nothing can be read."""
    if not any(s.name == name for s in ctx["spans"]):
        return None
    gaps = idle_by_span(ctx, anchor, bench)
    return None if gaps is None else gaps[0].get(f"repro.{name}", 0.0)
