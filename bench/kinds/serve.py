"""Serving: ``Engine.serve`` (continuous batching over the paged KV pool)
under an open-loop schedule made from ``--seed``.

The schedule is a fixed multiset of prompt lengths, output lengths and
arrival gaps (quantiles of the traffic file's distributions), put in an
order drawn from the seed, so every seed offers the same work.  Its
longest prompt and output reach the distributions' clips, so the padded
prompt length and the page table's width, and with them every program's
shapes, are the same in every run; set-up compiles exactly those
programs.  The window is one ``Engine.serve`` call: the arrivals of
``--seconds`` and their drain.  Each request is timed from when it was
due.

``correct``: once the window is closed and the engine is freed, the plain
reference runs over a sample of the finished requests (drawn from the
seed, the longest always in it), each prompt with its served tokens; the
number is the widest gap by which a served token's reference logit lies
below the reference's best at that position (greedy decoding).
"""
from __future__ import annotations

import gc
import math
import statistics
import time
from types import SimpleNamespace
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, reference, weights
from harness.device import memory_peak_bytes
from harness.program import model_config


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    nd = statistics.NormalDist()
    q = [median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.round(q), lo, hi).astype(np.int64)


def schedule(t: dict, vocab: int, seed: int, seconds: float):
    """[(rid, prompt, max_new_tokens, arrival)]: ``rate * seconds``
    requests whose sizes and gaps are the same multiset for every seed."""
    n = max(int(round(t["rate_per_s"] * seconds)), 2)
    p, o = t["prompt"], t["output"]
    plen = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                p["max"])
    olen = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                o["max"])
    if plen.max() != p["max"] or olen.max() != o["max"]:
        raise ValueError("the schedule's longest prompt and output must "
                         "reach the clips (raise sigma or the rate)")
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / t["rate_per_s"]
                     for i in range(n)])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 29]))
    plen, olen, gaps = (rng.permutation(plen), rng.permutation(olen),
                        rng.permutation(gaps))
    arrival = np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(plen[i])).astype(np.int32)
        out.append((i, prompt, int(olen[i]), float(arrival[i])))
    return out


class Server:
    """The engine at the cell's size, with bench-made weights."""

    def __init__(self, cell, seed: int):
        from repro.models.registry import get_model
        from repro.serving.engine import Engine, PagedConfig, ServeConfig
        from repro.serving.page_manager import pages_for
        t, c = cell.traffic, cell.config
        self.t, self.c, self.seed = t, c, seed
        self.cfg = model_config(c)
        self.model = get_model(self.cfg)
        abstract = jax.eval_shape(
            lambda: self.model.init(self.cfg, jax.random.PRNGKey(0)))
        self.param_key = weights.key_from_seed(seed, 0)
        self.make_params = jax.jit(weights.param_maker(
            abstract, c["initializer_range"]))
        self.params = self.make_params(self.param_key)
        ps = t["page_size"]
        self.S_pad = -(-t["prompt"]["max"] // ps) * ps
        self.max_new = t["output"]["max"]
        self.width = pages_for(self.S_pad + self.max_new, ps)
        self.num_pages = 1 + t["slots"] * self.width
        self.engine = Engine(
            self.cfg, self.params, max_len=self.S_pad + self.max_new,
            batch_size=t["slots"],
            serve=ServeConfig(max_new_tokens=self.max_new, temperature=0.0),
            paged=PagedConfig(page_size=ps, max_slots=t["slots"],
                              kv_dtype=t.get("kv_dtype")))

    def warm(self):
        """Compile and run once the programs ``Engine.serve`` will call at
        this schedule's shapes: the pool, prefill at the padded prompt
        length, and the decode step over every slot."""
        e, t = self.engine, self.t
        e._build_paged_programs(self.S_pad)
        i32 = lambda *s: jnp.zeros(s, jnp.int32)
        cache = self.model.init_paged_cache(self.cfg, self.num_pages,
                                            t["page_size"],
                                            kv_dtype=t.get("kv_dtype"))
        tok, cache = e._paged_prefill(
            self.params, cache, i32(1, self.S_pad), i32(1, self.width),
            jnp.asarray([self.S_pad], jnp.int32), i32(1))
        int(tok[0])  # the engine reads the first token back like this
        B = t["slots"]
        tok, cache = e._paged_decode(self.params, cache, i32(B, 1),
                                     i32(B, self.width), i32(B), i32(B),
                                     i32(B))
        jax.block_until_ready((tok, cache))
        del cache

    def requests(self, seconds: float):
        from repro.serving.scheduler import Request
        return [Request(rid=i, prompt=p, max_new_tokens=m, arrival=a)
                for i, p, m, a in schedule(self.t, self.c["vocab_size"],
                                           self.seed, seconds)]

    def free(self):
        self.engine = None
        self.params = None
        gc.collect()


def served_gaps(c: dict, params, prompt, tokens, lowp=None,
                insert_pad_at=None):
    """Per served token: the reference's best logit at that position minus
    its logit of the served token.  With ``lowp`` the token is instead the
    one the low-precision reference puts first (the control).
    ``insert_pad_at`` places a pad token (id 0) at that position of the
    context (a witness for where a served token was produced)."""
    ctx = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    n, P = len(tokens), len(prompt)
    idx = np.arange(P - 1, P - 1 + n)
    if insert_pad_at is not None:
        ctx = np.insert(ctx, insert_pad_at, 0)
        idx = np.where(idx >= insert_pad_at, idx + 1, idx)
    # causal: padding the context at its end changes no earlier position,
    # and a few padded lengths keep the reference to a few compiles
    padded = np.zeros(-(-len(ctx) // BUCKET) * BUCKET, np.int32)
    padded[:len(ctx)] = ctx
    lg = _ref_logits(c, params, jnp.asarray(padded[None]), None)[0]
    lg = lg[idx]
    if lowp is None:
        chosen = jnp.asarray(tokens)
    else:
        lo = _ref_logits(c, params, jnp.asarray(padded[None]), lowp)[0]
        chosen = jnp.argmax(lo[idx], -1)
    best = jnp.max(lg, -1)
    got = jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0]
    return np.asarray(best - got)


_LOGITS = {}
BUCKET = 1024


def _ref_logits(c, params, tokens, lowp):
    key = (id(c), lowp)
    if key not in _LOGITS:
        _LOGITS[key] = jax.jit(lambda p, t: reference.logits(c, p, t, lowp))
    return _LOGITS[key](params, tokens)


def sample_ids(outs: Dict, seed: int, k: int) -> List[int]:
    """``k`` finished requests drawn from the seed, the one with the most
    served tokens always among them."""
    ids = sorted(outs)
    longest = max(ids, key=lambda r: (len(outs[r].tokens), r))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 31]))
    rest = [r for r in ids if r != longest]
    pick = list(rng.choice(rest, min(k - 1, len(rest)), replace=False))
    return [longest] + sorted(int(r) for r in pick)


def check_numbers(c, params, reqs, outs, ids, lowp=None) -> Dict:
    by_id = {r.rid: r for r in reqs}
    gaps = [served_gaps(c, params, by_id[i].prompt, outs[i].tokens, lowp)
            for i in ids]
    return {"served_logit_gap": float(max(g.max() for g in gaps)),
            "served_tokens_checked": int(sum(len(g) for g in gaps))}


def run(env) -> dict:
    cell, t = env.cell, env.cell.traffic
    clock = time.perf_counter
    srv = Server(cell, env.seed)
    reqs = srv.requests(env.seconds if not env.trace
                        else min(env.seconds, t["trace_seconds"]))
    srv.warm()
    compiles = env.compile_counter()
    trace_cm = env.tracer() if env.trace else None
    if trace_cm is not None:
        trace_cm.__enter__()
    t_w0 = clock()
    setup_s = t_w0 - env.t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        outs = srv.engine.serve(reqs)
    t_w1 = clock()
    if trace_cm is not None:
        trace_cm.__exit__(None, None, None)
    n_compiles = compiles()
    mem = memory_peak_bytes(env.devices[:cell.chips])
    srv.free()
    params = srv.make_params(srv.param_key)
    ids = sample_ids(outs, env.seed, t["check_requests"])
    t_r0 = clock()
    numbers = check_numbers(cell.config, params, reqs, outs, ids)
    ref_s = clock() - t_r0
    correct, rows = compare.judge(
        {"served_logit_gap": numbers["served_logit_gap"]},
        cell.limits["numbers"])
    failed = sum(len(outs.get(r.rid).tokens) != r.max_new_tokens
                 if r.rid in outs else 1 for r in reqs)
    ctx = SimpleNamespace(
        kind="serve", cell=cell, config=cell.config, traffic=t,
        chips=cell.chips, setup_s=setup_s, window_s=t_w1 - t_w0,
        requests=reqs, outputs=outs)
    return dict(ctx=ctx, correct=correct and failed == 0, checks=rows,
                attempted=len(reqs), failed=failed, memory_peak_bytes=mem,
                compiles_in_window=n_compiles,
                notes={"served_tokens_checked":
                       numbers["served_tokens_checked"],
                       "requests_checked": len(ids),
                       "reference_s": round(ref_s, 2),
                       "window_s": round(t_w1 - t_w0, 3)})


def witness(cell, seed: int, seconds: float, static_requests: int = 1):
    """Where the served tokens come from, for a run whose tokens disagree
    with the reference: the widest gap of the served tokens against the
    reference (as ``correct`` reads it), against the reference given a pad
    token at the prompt's end (the context the paged decode attends when
    it starts one position late), and the gaps of the program's static
    path (``Engine.generate``, dense cache) on the same prompts."""
    from repro.serving.engine import Engine, ServeConfig
    srv = Server(cell, seed)
    reqs = srv.requests(seconds)
    srv.warm()
    outs = srv.engine.serve(reqs)
    srv.free()
    params = srv.make_params(srv.param_key)
    ids = sample_ids(outs, seed, cell.traffic["check_requests"])
    by_id = {r.rid: r for r in reqs}
    served = [served_gaps(cell.config, params, by_id[i].prompt,
                          outs[i].tokens) for i in ids]
    padded = [served_gaps(cell.config, params, by_id[i].prompt,
                          outs[i].tokens,
                          insert_pad_at=len(by_id[i].prompt)) for i in ids]
    static = []
    for i in ids[-static_requests:] if static_requests else []:
        r = by_id[i]
        eng = Engine(srv.cfg, params, max_len=len(r.prompt)
                     + r.max_new_tokens, batch_size=1,
                     serve=ServeConfig(max_new_tokens=r.max_new_tokens,
                                       temperature=0.0))
        toks = eng.generate(r.prompt[None])[0].tolist()
        static.append(served_gaps(cell.config, params, r.prompt, toks))
    return {"requests": len(ids),
            "served_tokens": int(sum(len(g) for g in served)),
            "served_gap_max": float(max(g.max() for g in served)),
            "served_gap_from_2nd_token": float(max(
                (g[1:].max() if len(g) > 1 else 0.0) for g in served)),
            "first_token_gap_max": float(max(g[0] for g in served)),
            "pad_context_gap_max": float(max(g.max() for g in padded)),
            "static_path_gap_max": (float(max(g.max() for g in static))
                                    if static else None)}
