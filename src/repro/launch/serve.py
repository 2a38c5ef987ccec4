"""Serving entrypoint: batched continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --reduced \
      --requests 6 --max-new 8
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.models.config import ModelConfig
from repro.models.api import build_model
from repro.serving.engine import Engine, Request


def serve(cfg: ModelConfig, requests: list[Request], *, slots: int,
          max_len: int, seed: int = 0) -> Engine:
    """Answer ``requests`` (in place: each ``Request.out`` fills) with a
    continuous-batching :class:`Engine` over ``cfg``'s model, weights drawn
    from ``seed``.  Requests beyond ``slots`` wait for free slots.  Returns
    the drained engine: its cache still holds every answered request's KV
    rows in the slot that served it last."""
    if cfg.family in ("vlm", "audio"):
        raise ValueError("use examples/ for the stub-frontend families")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    eng = Engine(model, params, batch_slots=slots, max_len=max_len)

    def busy() -> bool:
        return any(r is not None and not r.done for r in eng.slot_req)

    pending = list(requests)
    while pending or busy():
        n = eng.admit(pending)
        pending = pending[n:]
        while eng.tick():
            pass
        if n == 0 and not busy():
            break
    return eng


def main():
    from repro.launch.compile_cache import use_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    rng = np.random.default_rng(0)
    reqs = [
        Request(i, rng.integers(0, cfg.vocab_size, 12).astype(np.int32), max_new=args.max_new)
        for i in range(args.requests)
    ]
    try:
        serve(cfg, reqs, slots=args.slots, max_len=args.max_len)
    except ValueError as e:
        raise SystemExit(str(e))
    for r in reqs:
        print(f"req {r.rid}: {r.out}")


if __name__ == "__main__":
    main()
