"""Serving CLI: continuous-batching engine (default) or the legacy
static-batch greedy path (``--legacy``).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
      --requests 8 --batch 4 --prompt-len 64 --gen 32

``--batch`` keeps its historical meaning on both paths: the decode batch
width (engine slot count / legacy static batch).  The engine path admits
``--requests`` ragged requests through the prompt bucket ladder and
backfills slots as generations finish; the legacy path is kept verbatim as
the parity oracle (tests) and the static-batch baseline (bench_serve).

``--replicas N`` (or ``--disaggregate``) serves through the Router over N
replicas — each with ``--batch`` slots — under ``--policy`` admission;
``--disaggregate`` splits every serving unit into a prefill-role +
decode-role replica pair.  ``--metrics-jsonl PATH`` streams one JSONL row
per fused decode step (per replica) plus a final summary row, readable
back with ``core.telemetry.read_metrics_jsonl``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch, reduced as reduce_cfg
from repro.data import SyntheticCorpus
from repro.models import model_zoo
from repro.serve import (InferenceEngine, Request, Router, SamplingParams,
                         SchedulerConfig, make_replicas)
from repro.serve.policies import POLICIES
from repro.serve.router import ROUTES


class _JsonlWriter:
    """Append-one-row-per-call JSONL sink for Replica.on_step_metrics."""

    def __init__(self, path: str):
        self._f = open(path, "w")

    def __call__(self, row: dict) -> None:
        self._f.write(json.dumps(row) + "\n")

    def close(self) -> None:
        self._f.flush()
        self._f.close()


def serve(arch: str, use_reduced: bool, batch: int, prompt_len: int,
          gen_tokens: int, cache_len: int = 0, seed: int = 0,
          quiet: bool = False):
    """Legacy static-batch greedy decode (the engine's parity oracle)."""
    spec = get_arch(arch)
    cfg = reduce_cfg(spec.model) if use_reduced else spec.model
    model = model_zoo.build_model(cfg, dtype=jnp.float32, remat="none")
    params = model_zoo.init_params(jax.random.PRNGKey(seed), cfg)
    cache_len = cache_len or prompt_len + gen_tokens

    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seq_len=prompt_len,
                             seed=seed)
    prompts = corpus.batch(0, batch)["tokens"]  # (B, prompt_len)

    prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len=cache_len))
    decode = jax.jit(lambda p, c, t: model.decode(p, c, t))

    t0 = time.time()
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    logits.block_until_ready()
    t_prefill = time.time() - t0

    out_tokens = []
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    t1 = time.time()
    for _ in range(gen_tokens):
        out_tokens.append(np.asarray(tok)[:, 0])
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.time() - t1

    gen = np.stack(out_tokens, axis=1)
    if not quiet:
        print(f"arch={cfg.name} batch={batch} prompt={prompt_len} "
              f"gen={gen_tokens}")
        print(f"prefill: {t_prefill*1e3:.1f} ms "
              f"({batch*prompt_len/max(t_prefill,1e-9):.0f} tok/s)")
        print(f"decode:  {t_decode*1e3:.1f} ms total, "
              f"{batch*gen_tokens/max(t_decode,1e-9):.0f} tok/s")
        print("sample:", gen[0][:16].tolist())
    return {"prefill_s": t_prefill, "decode_s": t_decode,
            "decode_tok_s": batch * gen_tokens / max(t_decode, 1e-9),
            "generated": gen}


def make_requests(cfg, n_requests: int, prompt_len: int, gen_tokens: int,
                  seed: int = 0, ragged: bool = True,
                  sampling: SamplingParams = SamplingParams()):
    """Synthetic workload: ``n_requests`` prompts; when ``ragged``, prompt
    and generation lengths vary per request (the continuous-batching case —
    the paper's length heterogeneity at serving time)."""
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, seq_len=prompt_len,
                             seed=seed)
    prompts = np.asarray(corpus.batch(0, n_requests)["tokens"])
    reqs = []
    for i in range(n_requests):
        plen = prompt_len
        mt = gen_tokens
        if ragged:
            plen = max(4, prompt_len - (i % 4) * max(prompt_len // 6, 1))
            mt = max(1, gen_tokens - (i % 3) * max(gen_tokens // 4, 1))
        reqs.append(Request(uid=i, tokens=tuple(int(t) for t in
                                                prompts[i, :plen]),
                            max_tokens=mt, sampling=sampling))
    return reqs


def serve_engine(arch: str, use_reduced: bool, n_slots: int, prompt_len: int,
                 gen_tokens: int, n_requests: int = 0, cache_len: int = 0,
                 seed: int = 0, ragged: bool = True,
                 sampling: SamplingParams = SamplingParams(),
                 sched: SchedulerConfig = None, prefill_batch: int = 1,
                 decode_backend: str = "", paged: bool = False,
                 page_size: int = 64, n_pages: int = 0,
                 policy: str = "fcfs", metrics_jsonl: str = "",
                 quiet: bool = False):
    """Continuous-batching serve: the thin driver over InferenceEngine."""
    spec = get_arch(arch)
    cfg = reduce_cfg(spec.model) if use_reduced else spec.model
    n_requests = n_requests or n_slots
    cache_len = cache_len or prompt_len + gen_tokens
    sched = sched or SchedulerConfig(
        n_slots=n_slots, cache_len=cache_len,
        min_prompt_bucket=min(16, max(prompt_len // 4, 1)),
        round_multiple=max(prompt_len // 4, 8),
        prefill_batch=prefill_batch, paged=paged,
        page_size=page_size, n_pages=n_pages, policy=policy)
    engine = InferenceEngine.from_arch(arch, use_reduced=use_reduced,
                                       seed=seed, cfg=sched,
                                       decode_backend=decode_backend or None)
    writer = _JsonlWriter(metrics_jsonl) if metrics_jsonl else None
    if writer is not None:
        engine.on_step_metrics = writer
    reqs = make_requests(cfg, n_requests, prompt_len, gen_tokens, seed=seed,
                         ragged=ragged, sampling=sampling)
    t0 = time.time()
    results = engine.run(reqs)
    wall = time.time() - t0
    s = engine.stats
    if writer is not None:
        writer({"summary": True, "wall_s": wall,
                "generated_tokens": s.generated_tokens,
                "decode_steps": s.decode_steps,
                "slot_errors": s.slot_errors, "shed": s.shed})
        writer.close()
    if not quiet:
        print(f"arch={cfg.name} slots={n_slots} requests={n_requests} "
              f"buckets={engine.scheduler.ladder}")
        if sched.paged:
            from repro.serve import cache_nbytes
            print(f"paged:   {sched.resolved_n_pages} pages x "
                  f"{sched.page_size} tokens "
                  f"({sched.resolved_n_pages * sched.page_size} pool tokens "
                  f"vs {n_slots * sched.cache_len} dense; "
                  f"cache {cache_nbytes(engine.cache)/1e6:.2f} MB)")
        print(f"prefill: {s.prefill_s*1e3:.1f} ms ({s.prefill_tok_s:.0f} "
              f"tok/s over {s.prefill_tokens} prompt tokens)")
        print(f"decode:  {s.decode_s*1e3:.1f} ms, {s.decode_tok_s:.0f} tok/s "
              f"({s.generated_tokens} tokens, {s.decode_steps} fused steps)")
        print(f"latency: p50={s.latency_percentile(50)*1e3:.1f} ms "
              f"p95={s.latency_percentile(95)*1e3:.1f} ms per token")
        print("sample:", results[0].tokens[:16])
    return {"wall_s": wall, "prefill_s": s.prefill_s, "decode_s": s.decode_s,
            "prefill_tok_s": s.prefill_tok_s, "decode_tok_s": s.decode_tok_s,
            "p50_s": s.latency_percentile(50),
            "p95_s": s.latency_percentile(95),
            "results": results, "stats": s}


def serve_router(arch: str, use_reduced: bool, n_slots: int, prompt_len: int,
                 gen_tokens: int, n_requests: int = 0, cache_len: int = 0,
                 seed: int = 0, ragged: bool = True,
                 sampling: SamplingParams = SamplingParams(),
                 replicas: int = 2, policy: str = "fcfs",
                 route: str = "least-loaded", disaggregate: bool = False,
                 prefill_batch: int = 1, paged: bool = False,
                 page_size: int = 64, n_pages: int = 0,
                 metrics_jsonl: str = "", quiet: bool = False):
    """Routed serve: N replicas (each ``n_slots`` wide) behind the Router."""
    spec = get_arch(arch)
    cfg = reduce_cfg(spec.model) if use_reduced else spec.model
    n_requests = n_requests or replicas * n_slots
    cache_len = cache_len or prompt_len + gen_tokens
    sched = SchedulerConfig(
        n_slots=n_slots, cache_len=cache_len,
        min_prompt_bucket=min(16, max(prompt_len // 4, 1)),
        round_multiple=max(prompt_len // 4, 8),
        prefill_batch=prefill_batch, paged=paged,
        page_size=page_size, n_pages=n_pages, policy=policy)
    model = model_zoo.build_model(cfg, dtype=jnp.float32, remat="none")
    params = model_zoo.init_params(jax.random.PRNGKey(seed), cfg)
    router = Router(make_replicas(model, params, sched, replicas,
                                  disaggregate=disaggregate), route=route)
    writer = _JsonlWriter(metrics_jsonl) if metrics_jsonl else None
    if writer is not None:
        for rep in router.replicas:
            rep.on_step_metrics = writer
    reqs = make_requests(cfg, n_requests, prompt_len, gen_tokens, seed=seed,
                         ragged=ragged, sampling=sampling)
    t0 = time.time()
    results = router.run(reqs)
    wall = time.time() - t0
    summary = router.summary()
    if writer is not None:
        writer(dict(summary, summary=True, wall_s=wall))
        writer.close()
    if not quiet:
        agg = summary["aggregate"]
        print(f"arch={cfg.name} replicas={replicas} slots={n_slots}/replica "
              f"policy={policy} route={route} "
              f"disaggregate={disaggregate} requests={n_requests}")
        print(f"routed={summary['routed']} spilled={summary['spilled']} "
              f"shed={summary['shed']}")
        print(f"prefill: {agg['prefill_s']*1e3:.1f} ms   "
              f"decode: {agg['decode_s']*1e3:.1f} ms, "
              f"{agg['generated_tokens']} tokens, "
              f"{agg['decode_steps']} fused steps, "
              f"slot_errors={agg['slot_errors']}")
        for name, row in summary["replicas"].items():
            print(f"  {name}: admitted={row['admitted']} "
                  f"{row['decode_tok_s']:.0f} tok/s "
                  f"p95={row['p95_step_s']*1e3:.1f} ms")
        print("sample:", results[0].tokens[:16])
    return {"wall_s": wall, "results": results, "summary": summary,
            "router": router}


def main(argv=None) -> int:
    """Returns 1 when any request finished with an error or any replica
    retired a slot on an error: the engine contains a failing request so
    the batch keeps going, and the exit code is what reports it."""
    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="smollm-360m")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4,
                   help="decode width: engine slot count / legacy batch")
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--cache-len", type=int, default=0,
                   help="per-slot cache capacity (0 = prompt+gen)")
    p.add_argument("--legacy", action="store_true",
                   help="static-batch greedy path instead of the engine")
    p.add_argument("--requests", type=int, default=0,
                   help="engine: number of requests (0 = --batch)")
    p.add_argument("--uniform", action="store_true",
                   help="engine: identical prompt/gen lengths per request")
    p.add_argument("--prefill-batch", type=int, default=1,
                   help="engine: admit up to k same-bucket requests as one "
                        "(k, bucket) prefill call")
    p.add_argument("--decode-backend", default="",
                   choices=["", "reference", "kernel", "kernel_interpret"],
                   help="engine: override ModelConfig.decode_backend "
                        "(default: the arch preset's value)")
    p.add_argument("--paged", action="store_true",
                   help="engine: paged KV pool + per-slot page tables "
                        "instead of dense (n_slots, cache_len) rows")
    p.add_argument("--page-size", type=int, default=64,
                   help="engine: tokens per KV page (with --paged)")
    p.add_argument("--n-pages", type=int, default=0,
                   help="engine: KV pool size in pages (0 = dense-"
                        "equivalent n_slots * ceil(cache_len/page_size))")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through the Router over N replicas "
                        "(each --batch slots wide)")
    p.add_argument("--policy", default="fcfs", choices=list(POLICIES),
                   help="admission policy (serve/policies.py)")
    p.add_argument("--route", default="least-loaded", choices=list(ROUTES),
                   help="router replica selection")
    p.add_argument("--disaggregate", action="store_true",
                   help="split each serving unit into a prefill-role + "
                        "decode-role replica pair")
    p.add_argument("--metrics-jsonl", default="",
                   help="stream one JSONL metrics row per fused decode "
                        "step (+ a summary row) to this path")
    args = p.parse_args(argv)

    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, seed=args.seed)
    if args.legacy:
        serve(args.arch, args.reduced, args.batch, args.prompt_len, args.gen,
              cache_len=args.cache_len, seed=args.seed)
        return 0
    if args.replicas > 1 or args.disaggregate:
        out = serve_router(args.arch, args.reduced, args.batch, args.prompt_len,
                     args.gen, n_requests=args.requests,
                     cache_len=args.cache_len, seed=args.seed,
                     ragged=not args.uniform, sampling=sp,
                     replicas=args.replicas, policy=args.policy,
                     route=args.route, disaggregate=args.disaggregate,
                     prefill_batch=args.prefill_batch, paged=args.paged,
                     page_size=args.page_size, n_pages=args.n_pages,
                     metrics_jsonl=args.metrics_jsonl)
        slot_errors = out["summary"]["aggregate"]["slot_errors"]
    else:
        out = serve_engine(args.arch, args.reduced, args.batch, args.prompt_len,
                     args.gen, n_requests=args.requests,
                     cache_len=args.cache_len, seed=args.seed,
                     ragged=not args.uniform, sampling=sp,
                     prefill_batch=args.prefill_batch,
                     decode_backend=args.decode_backend, paged=args.paged,
                     page_size=args.page_size, n_pages=args.n_pages,
                     policy=args.policy, metrics_jsonl=args.metrics_jsonl)
        slot_errors = out["stats"].slot_errors
    failed = sum(r.finish_reason == "error" for r in out["results"])
    if failed or slot_errors:
        print(f"serve failed: {failed} of {len(out['results'])} requests "
              f"finished with an error, slot_errors={slot_errors}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
