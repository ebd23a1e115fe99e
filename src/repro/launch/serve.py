"""Serving launcher: stands up the MLaaS engine for any arch (decoder modes)
or GECToR (encoder mode) and optionally runs the load-test ladder against
it — the deployable version of examples/serve_poc.py.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --requests 8 --temperature 0.7 --stream

``--smoke`` (the default) serves the reduced variant; ``--no-smoke`` serves
the published widths (e.g. ``--arch qwen2-0.5b --no-smoke`` on a TPU).

Decoder requests go through the v2 API (GenerationRequest -> RequestHandle
-> GenerationResult) and are served by the step-level continuous-batching
scheduler unless --no-continuous selects the batch-at-a-time worker.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, get_config
from repro.core.loadtest import format_table, run_ladder
from repro.models import init_params
from repro.serving import EngineConfig, SamplingParams, ServingEngine
from repro.training.checkpoint import restore


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gector-base",
                    choices=ARCHS + ["gector-base"])
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (default); --no-smoke serves the "
                         "published widths")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--ladder", type=int, nargs="*", default=None)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-inflight", type=int, default=None)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="print the first request's tokens as they arrive")
    ap.add_argument("--no-continuous", action="store_true",
                    help="batch-at-a-time decoder worker (A/B baseline)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.ckpt:
        params = restore(args.ckpt)["params"]
        if "encoder" in params:          # gector checkpoint
            params = params["encoder"]
    else:
        params = init_params(cfg, jax.random.PRNGKey(0))
    mode = "encoder" if cfg.arch_type == "encoder" else "decoder"
    eng = ServingEngine(cfg, params,
                        EngineConfig(mode=mode, max_batch=args.max_batch,
                                     max_inflight=args.max_inflight,
                                     max_new_tokens=args.max_new_tokens,
                                     continuous=not args.no_continuous))
    try:
        sentences = [np.random.randint(0, cfg.vocab_size,
                                       (np.random.randint(8, 32),))
                     for _ in range(max(args.requests, 32))]
        if args.ladder:
            cells = run_ladder(eng, sentences, ladder=tuple(args.ladder),
                               repeats=1)
            print(format_table(cells))
        elif mode == "decoder":
            sp = SamplingParams(eos_id=args.eos_id,
                                temperature=args.temperature,
                                top_k=args.top_k, seed=args.seed)
            handles = [eng.generate(s, sp)
                       for s in sentences[: args.requests]]
            if args.stream and handles:
                print("request[0] stream:", end=" ", flush=True)
                for tok in handles[0]:
                    print(tok, end=" ", flush=True)
                print()
            res = None
            for h in handles:
                res = h.result(timeout=600)
            if res is not None:
                t = res.timing
                print(f"last request: {len(res.tokens)} tokens, "
                      f"finish={res.finish_reason}, "
                      f"queue {t.queue_s * 1e3:.1f}ms"
                      f" | prefill {t.prefill_s * 1e3:.1f}ms"
                      f" | decode {t.decode_s * 1e3:.1f}ms")
            print("metrics:", eng.metrics())
        else:
            futs = [eng.submit(s) for s in sentences[: args.requests]]
            for f in futs:
                f.result(timeout=600)
            print("metrics:", eng.metrics())
    finally:
        eng.close()


if __name__ == "__main__":
    main()
