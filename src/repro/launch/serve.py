"""Serving driver: batched requests through prefill + decode with telemetry.

CPU-sized example:
  PYTHONPATH=src python -m repro.launch.serve --arch glm4_9b --smoke \\
      --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from ..configs import get_config
from ..core import BigRootsAnalyzer, JAX_FEATURES, render_markdown, summarize
from ..models import Model, smoke_variant
from ..serve import Diagnosis
from ..serve.engine import Request, ServeEngine
from ..telemetry.events import StepTelemetry
from ..telemetry.sampler import SystemSampler
from ..telemetry.timeline import ResourceTimeline
from .cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="glm4_9b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if cfg.enc_layers:
        raise SystemExit("serve driver targets decoder-only archs")
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))

    timeline = ResourceTimeline()
    telem = StepTelemetry("host0", timeline=timeline, window=64,
                          streaming=True)
    rng = np.random.default_rng(args.seed)
    requests = [
        Request(
            request_id=f"r{i}",
            prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]

    engine = ServeEngine(
        model, params,
        max_len=args.prompt_len + args.max_new + 8,
        batch_size=args.batch_size,
        temperature=args.temperature,
        telemetry=telem,
        diagnosis=Diagnosis.local(
            BigRootsAnalyzer(JAX_FEATURES, timelines=timeline)
        ),
    )
    with SystemSampler("host0", timeline, interval=0.25):
        t0 = time.time()
        done = 0
        for i in range(0, len(requests), args.batch_size):
            batch = requests[i : i + args.batch_size]
            engine.run(batch, step_offset=i * args.max_new)
            done += len(batch)
        wall = time.time() - t0

    analyzer = BigRootsAnalyzer(JAX_FEATURES, timelines=timeline)
    summary = summarize(analyzer.analyze(telem.trace))
    toks = sum(len(r.output) for r in requests)
    print(render_markdown(summary, title=f"BigRoots serve report — {cfg.name}"))
    print(json.dumps({
        "arch": cfg.name,
        "requests": done,
        "generated_tokens": toks,
        "wall_seconds": wall,
        "tokens_per_second": toks / wall if wall else 0.0,
        "prefill_seconds_last_batch": engine.last_prefill_seconds,
        "stragglers": summary.num_stragglers,
        "live_root_causes": [
            {"task": c.task_id, "feature": c.feature, "value": c.value}
            for c in engine.live_root_causes
        ],
    }, indent=2))


if __name__ == "__main__":
    main()
