"""Serving CLI: static-batch oracle + the continuous-batching engine.

Static (the oracle the engine is tested against):
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b \
        --reduced --batch 4 --prompt-len 32 --gen 16

Continuous batching over the paged KV cache (``repro.serve``):
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
        --reduced --engine continuous --attention paged --requests 8 --gen 16

Serve-under-fire drills (the CI ``serve-chaos`` job runs both):

    # supervised chaos: inject a decode hang + crash; the engine rebuilds
    # from host truth and the run must stay token-identical to the oracle
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
        --reduced --engine continuous --requests 4 --gen 8 \
        --chaos hang:3,crash:6 --watchdog-s 30
    # -> prints "SERVE_DRILL token_identical=true ...", exit 0
    # -> exit 3 when any completed stream diverges from the oracle

    # unsupervised: the same fault must fail LOUDLY (exit 2), never wedge
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \
        --reduced --engine continuous --requests 4 --gen 8 \
        --chaos hang:1 --watchdog-s 30 --no-supervise   # -> exit 2
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.tl_step import make_serve_step
from repro.models import build_model

# one compiled serve step per config — generate() must never re-jit per call
_STEP_CACHE: dict = {}


def _serve_step_fn(model, cfg):
    fn = _STEP_CACHE.get(cfg.name)
    if fn is None:
        fn = jax.jit(make_serve_step(model, cfg))
        _STEP_CACHE[cfg.name] = fn
    return fn


def generate(model, cfg, params, prompts, gen_len: int, *, temperature=0.0,
             key=None, seeds=None):
    """prompts: (B, P) int32.  Greedy (or sampled) continuation, (B, gen_len).

    Sampling uses per-row RNG streams from ``repro.serve.sampling`` — row b
    draws from ``fold_in(fold_in(key, seeds[b]), step)`` — so a request's
    stream depends only on (key, seed, step), exactly matching the
    continuous engine's streams.  ``key=None`` defaults to ``PRNGKey(0)``;
    ``seeds`` defaults to ``arange(B)``.
    """
    from repro.serve.sampling import request_key, sample_tokens
    B, P = prompts.shape
    max_len = P + gen_len
    cache = model.init_cache(B, max_len)
    if cfg.is_encdec:
        frames = jnp.zeros((B, cfg.frontend_tokens, cfg.d_model))
        logits, cache = model.prefill(params, cache, prompts, frames)
    else:
        logits, cache = model.prefill(params, cache, prompts)
    step_fn = _serve_step_fn(model, cfg)

    if temperature > 0:
        base = jax.random.PRNGKey(0) if key is None else key
        seeds = jnp.arange(B) if seeds is None else jnp.asarray(seeds)
        keys = jax.vmap(lambda s: request_key(base, s))(seeds)
    else:
        keys = jnp.zeros((B, 2), jnp.uint32)
    temps = jnp.full((B,), temperature, jnp.float32)

    out = []
    tok = sample_tokens(logits, keys, jnp.zeros((B,), jnp.int32), temps)
    for t in range(gen_len):
        out.append(tok)
        if t == gen_len - 1:
            break
        logits, cache = step_fn(params, cache, tok,
                                jnp.asarray(P + t, jnp.int32))
        tok = sample_tokens(logits, keys, jnp.full((B,), t + 1, jnp.int32),
                            temps)
    return jnp.stack(out, axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the architecture's published widths instead of "
                         "the reduced CPU preset")
    ap.add_argument("--engine", choices=["static", "continuous"],
                    default="static")
    ap.add_argument("--attention", choices=["paged", "dense"],
                    default="paged", help="continuous-engine decode path")
    ap.add_argument("--batch", "--requests", dest="batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=128)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--decode-priority", type=int, default=1)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO: absolute deadline = submit time "
                         "+ this many ms; past it requests are shed/aborted")
    ap.add_argument("--chaos", default=None,
                    help="scripted decode faults, e.g. hang:3,crash:6 "
                         "(see repro.serve.faults.parse_chaos)")
    ap.add_argument("--watchdog-s", type=float, default=30.0,
                    help="decode-step watchdog deadline (hang detection)")
    ap.add_argument("--no-supervise", action="store_true",
                    help="disable fault supervision: an injected fault "
                         "fails loudly (exit 2) instead of recovering")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)

    if args.engine == "static":
        t0 = time.time()
        tokens = generate(model, cfg, params, prompts, args.gen,
                          temperature=args.temperature, key=key)
        dt = time.time() - t0
        print(f"generated {tokens.shape} in {dt:.2f}s "
              f"({args.batch*args.gen/dt:.1f} tok/s)")
        print(np.asarray(tokens[:2]))
        return tokens

    from repro.serve import (Request, ServeEngine, ServeFault,
                             ServeFaultSpec, parse_chaos)
    faults = None
    if args.chaos:
        faults = ServeFaultSpec(seed=args.seed,
                                drills=parse_chaos(args.chaos))
    eng = ServeEngine(model, cfg, params, num_pages=args.num_pages,
                      page_size=args.page_size, max_slots=args.max_slots,
                      max_len=args.prompt_len + args.gen,
                      attention=args.attention,
                      decode_priority=args.decode_priority, seed=args.seed,
                      faults=faults, watchdog_s=args.watchdog_s,
                      supervise=not args.no_supervise)
    t0 = time.time()
    for r in range(args.batch):
        now = time.time()
        deadline = (None if args.deadline_ms is None
                    else now + args.deadline_ms / 1e3)
        eng.submit(Request(rid=r, prompt=np.asarray(prompts[r]),
                           max_new_tokens=args.gen,
                           temperature=args.temperature, seed=r,
                           arrival=now, deadline=deadline))
    try:
        results = eng.run()
    except ServeFault as e:
        print(f"FATAL: unsupervised serving fault\n{e}", file=sys.stderr)
        raise SystemExit(2)
    dt = time.time() - t0
    stats = eng.stats()
    n_tok = sum(len(r.tokens) for r in results.values())
    print(f"served {args.batch} requests / {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s, engine={args.engine}, "
          f"attention={args.attention})")
    print(f"  shed={stats['n_shed']} deadline_aborts="
          f"{stats['n_deadline_aborts']} preempted={stats['n_preempted']} "
          f"restored={stats['n_restored']} rebuilds={stats['n_rebuilds']}"
          + (f" shed_rids={stats['shed_rids']}" if stats['shed_rids']
             else ""))
    for rep in eng.recoveries:
        d = rep.as_dict()
        print(f"  recovery step={d['step']} cause={d['cause']} "
              f"survivors={d['n_survivors']} detect={d['detect_s']}s "
              f"rebuild={d['rebuild_s']}s reprefill={d['reprefill_s']}s "
              f"first_token={d['first_token_s']}s")
    for r in sorted(results.values(), key=lambda r: r.rid)[:2]:
        print(f"  rid={r.rid} [{r.finish_reason}] {r.tokens}")

    if args.chaos:
        # drill verification: every stream the engine completed (and every
        # partial prefix) must be bit-identical to the fault-free oracle
        oracle = np.asarray(generate(
            model, cfg, params, prompts, args.gen,
            temperature=args.temperature, key=key,
            seeds=list(range(args.batch))))
        identical = True
        for r in results.values():
            want = oracle[r.rid][:len(r.tokens)].tolist()
            full = (r.finish_reason == "length"
                    and len(r.tokens) == args.gen)
            if r.tokens != want or (r.finish_reason == "length"
                                    and not full):
                identical = False
                print(f"  DIVERGED rid={r.rid}: engine={r.tokens} "
                      f"oracle={want}", file=sys.stderr)
        print(f"SERVE_DRILL token_identical={str(identical).lower()} "
              f"rebuilds={stats['n_rebuilds']} shed={stats['n_shed']} "
              f"completed={sum(1 for r in results.values() if r.finish_reason in ('eos', 'length'))}"
              f"/{args.batch}")
        if not identical:
            raise SystemExit(3)
    return results


if __name__ == "__main__":
    main()
