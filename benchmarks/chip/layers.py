"""Arithmetic the per-layer metric readers share.  A reader gets one
``ctx`` dict from the traced run: the session's counts and the program's
``Tracer`` spans inside the window (``window_s`` long, host clock), the
trace reduction (``trace``, see ``xplane.py``), the device's ``peaks``,
the cell's ``config`` and ``mix``, and the chips used (``chips``).
"""

from __future__ import annotations


def span_s(ctx: dict, *names: str) -> float:
    """Seconds of the program's spans named ``names`` inside the window."""
    return sum(s.t1 - s.t0 for s in ctx["spans"] if s.name in names)


def per_frame_ms(ctx: dict, *names: str) -> float | None:
    frames = ctx["frames"]
    return span_s(ctx, *names) / frames * 1e3 if frames else None


def window_pct(ctx: dict, *names: str) -> float:
    return span_s(ctx, *names) / ctx["window_s"] * 100.0


def idle_pct(ctx: dict) -> float:
    """Share of the traced window in which no program ran on a device,
    averaged over the chips."""
    red = ctx["trace"]
    return (1.0 - red.busy_s / red.window_s) * 100.0
