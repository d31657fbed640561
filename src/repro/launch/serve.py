"""Serving launcher: batched prefill+decode for any assigned architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_7b --smoke \
        --batch 4 --new-tokens 16

--continuous switches to the production path (continuous batching over
the paged KV cache, per-request prompt/output lengths served from a
Poisson request stream; attention-family archs only); --kv-dtype int8
stores the paged cache block-quantized.  --mesh single/multi builds the
production mesh + serve policy (TPU target; the AOT compile path of the
same functions is exercised by launch/dryrun.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, smoke_model
from repro.dist.policies import make_serve_policy
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models.registry import get_model
from repro.serving.engine import Engine, PagedConfig, ServeConfig
from repro.serving.scheduler import Request


def _serve_continuous(engine, args, vocab):
    """Synthetic Poisson stream through Engine.serve."""
    rng = np.random.default_rng(0)
    t, reqs = 0.0, []
    for rid in range(args.requests):
        t += rng.exponential(1.0 / args.rate)
        plen = int(rng.integers(4, args.prompt_len + 1))
        reqs.append(Request(
            rid=rid, prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(2, args.new_tokens + 1)),
            arrival=t))
    t0 = time.time()
    outs = engine.serve(reqs)
    dt = time.time() - t0
    n_tok = sum(len(o.tokens) for o in outs.values())
    print(f"continuous: {len(reqs)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s on this backend, "
          f"kv_dtype={args.kv_dtype or 'dense'})")
    for rid in sorted(outs)[:4]:
        o = outs[rid]
        print(f"  req{rid}: ttft={o.ttft*1e3:.1f}ms "
              f"tokens={o.tokens[:8]}{'...' if len(o.tokens) > 8 else ''}")
    return {"requests": reqs, "outputs": outs, "seconds": dt}


def main(argv=None):
    run(argv)


def run(argv=None) -> dict:
    """Parse ``argv``, build the engine and serve; returns the engine and
    what it produced (chip_smoke.py drives it)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_7b", choices=ARCH_IDS)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV cache")
    ap.add_argument("--kv-dtype", default=None, choices=[None, "int8"],
                    help="paged KV storage dtype (--continuous only)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16,
                    help="stream length for --continuous")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate (req/s) for --continuous")
    args = ap.parse_args(argv)
    enable_compile_cache()

    bundle = get_config(args.arch)
    cfg = smoke_model(bundle.model) if args.smoke else bundle.model
    model = get_model(cfg)

    policy = None
    if args.mesh != "host":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        policy = make_serve_policy(mesh, dp_axes=dp_axes(mesh))

    params = model.init(cfg, jax.random.PRNGKey(0))
    engine = Engine(cfg, params, max_len=args.prompt_len + args.new_tokens,
                    batch_size=args.batch, policy=policy,
                    serve=ServeConfig(max_new_tokens=args.new_tokens,
                                      temperature=args.temperature),
                    paged=PagedConfig(page_size=args.page_size,
                                      max_slots=args.batch,
                                      kv_dtype=args.kv_dtype))
    if args.continuous:
        return dict(_serve_continuous(engine, args, cfg.vocab_size),
                    engine=engine)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    extra = {}
    if cfg.frontend == "vit_stub":
        extra["patch_embeds"] = np.zeros(
            (args.batch, cfg.frontend_tokens, cfg.d_model), np.float32)
    if cfg.family == "encdec":
        extra["frames"] = rng.normal(0, 1, (args.batch, args.prompt_len,
                                            cfg.d_model)).astype(np.float32)
    t0 = time.time()
    out = engine.generate(prompts, extra_inputs=extra or None)
    dt = time.time() - t0
    n_tok = out.size
    print(f"arch={args.arch} family={cfg.family} batch={args.batch}: "
          f"generated {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s on this backend)")
    for i, row in enumerate(out[:4]):
        print(f"  req{i}: {row[:12].tolist()}")
    return {"engine": engine, "tokens": out, "seconds": dt}


if __name__ == "__main__":
    main()
