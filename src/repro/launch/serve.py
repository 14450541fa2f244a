"""Serving driver: batched requests through the continuous-batching engine.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
      --requests 8 --max-new 12
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --full \
      --slots 4 --max-len 512        # published widths, one accelerator
"""

from __future__ import annotations

import argparse
import time
from typing import Any

import jax

from repro import configs as cfgs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.models.config import ModelConfig
from repro.serving import Request, ServingEngine

__all__ = ["build_engine", "make_requests", "main"]


def build_engine(arch: str, *, full: bool = False, slots: int = 4,
                 max_len: int = 128, seed: int = 0,
                 offload: Any | None = None) -> ServingEngine:
    """A ``ServingEngine`` over ``arch`` with weights drawn from ``seed``:
    the published config with ``full``, its reduced smoke config without."""
    cfg = cfgs.get_config(arch) if full else cfgs.get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    return ServingEngine(cfg, params, batch_slots=slots, max_len=max_len,
                         offload=offload)


def make_requests(cfg: ModelConfig, n: int, *, max_new: int,
                  seed: int = 1) -> list[Request]:
    """``n`` requests with prompts of 8, 12 and 16 tokens drawn from
    ``seed``."""
    rng = jax.random.PRNGKey(seed)
    reqs = []
    for rid in range(n):
        rng, k = jax.random.split(rng)
        plen = 8 + (rid % 3) * 4
        prompt = list(map(int, jax.random.randint(
            k, (plen,), 0, cfg.vocab_size)))
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    return reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b", choices=sorted(cfgs.ARCHS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    engine = build_engine(args.arch, full=not args.smoke, slots=args.slots,
                          max_len=args.max_len, seed=args.seed)
    t0 = time.time()
    for req in make_requests(engine.cfg, args.requests,
                             max_new=args.max_new, seed=args.seed + 1):
        engine.submit(req)
    done = engine.run_to_completion()
    dt = time.time() - t0
    tokens = sum(len(r.out_tokens) for r in done)
    for r in sorted(done, key=lambda r: r.rid)[:4]:
        print(f"[serve] rid={r.rid} prompt_len={len(r.prompt)} -> "
              f"{r.out_tokens}")
    print(f"[serve] {len(done)} requests, {tokens} tokens in {dt:.1f}s "
          f"({tokens/dt:.1f} tok/s, {args.slots} slots, "
          f"continuous batching)")


if __name__ == "__main__":
    main()
