#!/usr/bin/env python
"""Continuous-batching serving benchmark (tpucfn.serve).

Two workloads through the full Server → scheduler → engine path, ONE
JSON line out in the standard BENCH row schema:

* **Mixed** (the headline): Zipf-ish spread of prompt lengths,
  Poisson-ish arrival jitter deliberately OMITTED (open-loop arrivals
  would measure the queue, not the engine; every request is submitted
  up front so the scheduler stays saturated).  Produces
  ``serve_tokens_per_sec``.
* **Shared-prefix** (ISSUE 3 acceptance): every request opens with the
  same ``--shared-prefix-len`` system prompt.  Run once with the prefix
  cache OFF (and prefill batching at 1) and once ON (batching at
  ``--max-prefill-batch``), same engine, same prompts — the
  ``detail.shared_prefix`` block reports prefix hit rate, prefill calls
  per request, prefilled tokens per request, and TTFT for both, plus
  ``prefilled_tokens_reduction`` (the >= 2x acceptance number) and the
  ``ceil(requests / K)`` call ceiling batching is held to.

Compile warmup is excluded from every timed window: each phase's
buckets (and the copy_prefix program) are compiled by throwaway servers
on the SAME engine first, as the training benchmark warms up every
shape before its window opens.

``vs_baseline`` is 0.0: the reference repo was a training-only harness
with no serving number to compare against (detail.baseline_note says
so).  Meaningful throughput needs the real chip; on CPU this is a
correctness and scheduling-overhead bench.

* **Availability** (``--availability``, ISSUE 9): the serve-side
  analogue of ``ft_bench``'s MTTR split — a deterministic open-loop run
  (seeded exponential arrival trace) against TWO replicas behind the
  :class:`~tpucfn.serve.router.ReplicaRouter`, with replica 0 killed at
  the trace midpoint.  Emits its own BENCH row
  (``metric: serve_availability``) whose ``detail`` carries
  ``availability`` (fraction of ACCEPTED requests completing within
  deadline), the retry success rate, and the hedge win rate.

* **Speculative decoding** (``--spec``, ISSUE 14): three legs over one
  prompt set — plain decode, a self-draft (identical weights ⇒ the
  synthetic high-acceptance workload), and an adversarial nano draft
  with divergent weights (⇒ zero acceptance, the controller's worst
  case).  Every leg's outputs are asserted bit-identical (greedy spec
  decode's correctness contract), then two rc gates: the high-
  acceptance leg must reach >= 1.5x ``tokens_per_target_step`` vs
  plain, and the adversarial leg's measured TPOT must stay within
  1.3x of plain — the acceptance-driven controller shrinking k and
  then turning speculation off (amortized probes only) is what makes
  that bound real rather than hoped.

Usage: python benches/serve_bench.py [--preset tiny --requests 32 ...]
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _run_workload(engine, args, prompts, *, prefix_cache, max_prefill_batch,
                  max_new):
    """One timed pass over ``prompts`` through a fresh Server (fresh
    metrics + KV pool; jit caches ride on the shared engine)."""
    from tpucfn.serve import Server

    server = Server(engine, num_blocks=args.num_blocks,
                    block_size=args.block_size, prefix_cache=prefix_cache,
                    max_prefill_batch=max_prefill_batch)
    t0 = time.perf_counter()
    reqs = [server.submit(q, max_new_tokens=max_new) for q in prompts]
    server.run_until_idle()
    wall = time.perf_counter() - t0
    snap = server.metrics.snapshot()
    n = len(prompts)
    return {
        "wall_s": round(wall, 3),
        "failed": sum(1 for r in reqs if r.error is not None),
        "kv_blocks_leaked": server.kv.allocator.num_used,
        "kv_blocks_high_water": server.kv.allocator.high_water,
        "prefill_calls": int(snap["prefill_calls"]),
        "prefill_calls_per_request": round(snap["prefill_calls"] / n, 3),
        "prefilled_tokens_per_request": round(snap["prefilled_tokens"] / n, 3),
        "prefix_hit_rate": round(snap["prefix_hit_requests"] / n, 3),
        "prefix_hit_tokens_per_request": round(
            snap["prefix_hit_tokens"] / n, 3),
        "ttft_p50_s": snap["ttft_s"]["p50"],
        "ttft_p95_s": snap["ttft_s"]["p95"],
        "tokens_per_sec": round(snap["generated_tokens"] / wall, 3),
        "snapshot": snap,
        "slo": server.slo.snapshot(),
    }


def run_availability(args) -> int:
    """Open-loop availability drill: 2 replicas, seeded arrival trace,
    replica 0 killed after half the trace has been submitted.  Every
    count in the row is over ACCEPTED requests — admission rejections
    are the router doing its job, not lost availability."""
    import jax
    import numpy as np

    from tpucfn.serve import AdmissionError, ReplicaRouter, Server
    from tpucfn.serve.engine import ServeEngine, demo_llama_engine

    print(f"# backend={jax.default_backend()} availability drill "
          f"requests={args.avail_requests}", file=sys.stderr)
    cfg, engine = demo_llama_engine(args.preset, seed=args.seed,
                                    max_batch=args.max_batch,
                                    cache_len=args.cache_len,
                                    prefill_width=args.max_prefill_batch)
    engines = [engine,
               ServeEngine.from_llama(cfg, engine.params,
                                      max_batch=args.max_batch,
                                      cache_len=args.cache_len,
                                      prefill_width=args.max_prefill_batch)]

    def factory(i: int) -> Server:
        return Server(engines[i], num_blocks=args.num_blocks,
                      block_size=args.block_size, prefix_cache=True,
                      max_prefill_batch=args.max_prefill_batch)

    rs = np.random.RandomState(args.seed)
    prompts = [rs.randint(0, cfg.vocab_size,
                          rs.randint(args.prompt_len_lo,
                                     args.prompt_len_hi + 1)).tolist()
               for _ in range(args.avail_requests)]
    # Seeded open-loop arrival trace: exponential inter-arrivals, fixed
    # by --seed, so two runs submit the same prompts at the same
    # offsets — the arrival process is part of the drill's identity.
    gaps = rs.exponential(args.avail_interarrival_ms / 1000.0,
                          size=args.avail_requests)
    arrivals = np.cumsum(gaps)

    # Compile warmup outside the timed/measured window: both replicas'
    # buckets (each engine owns its own jit caches).
    from tpucfn.serve.scheduler import prefill_bucket
    for eng in engines:
        warm = Server(eng, num_blocks=args.num_blocks,
                      block_size=args.block_size, prefix_cache=False,
                      max_prefill_batch=args.max_prefill_batch)
        for b in sorted({prefill_bucket(len(q), args.cache_len)
                         for q in prompts}):
            warm.submit([1] * min(b, args.cache_len - 2), max_new_tokens=2)
        warm.run_until_idle()

    router = ReplicaRouter(factory, 2, retry_budget=args.retry_budget,
                           hedge_ms=args.hedge_ms,
                           breaker_cooldown_s=1.0)
    router.start()
    kill_at = args.avail_requests // 2
    reqs, rejected = [], 0
    t0 = time.perf_counter()
    killed_at_s = None
    for k, q in enumerate(prompts):
        if k == kill_at:
            killed_at_s = time.perf_counter() - t0
            router.kill_replica(0)
        lag = arrivals[k] - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        try:
            reqs.append(router.submit(q, max_new_tokens=args.max_new,
                                      deadline_s=args.avail_deadline_s))
        except AdmissionError:
            # ONLY admission rejections are tolerable here; a router
            # bug raising anything else must crash the bench, not be
            # tallied into a plausible-looking row
            rejected += 1
    for r in reqs:
        r.done.wait(args.avail_deadline_s + 30.0)
    wall = time.perf_counter() - t0
    router.stop()

    accepted = len(reqs)
    ok = sum(1 for r in reqs if r.status == "ok")
    dropped = sum(1 for r in reqs if r.status == "pending")
    retried = [r for r in reqs if r.retries > 0]
    retried_ok = sum(1 for r in retried if r.status == "ok")
    snap = router.snapshot()
    availability = ok / accepted if accepted else 0.0
    row = {
        "metric": "serve_availability",
        "value": round(availability, 4),
        "unit": "fraction of accepted requests completing within deadline",
        "vs_baseline": 0.0,
        "detail": {
            "baseline_note": "reference harness was training-only; no "
                             "published serving availability exists",
            "backend": jax.default_backend(),
            "preset": args.preset,
            "replicas": 2,
            "requests": args.avail_requests,
            "accepted": accepted,
            "rejected_at_submit": rejected,
            "availability": round(availability, 4),
            "dropped": dropped,
            "completed_ok": ok,
            "retried": len(retried),
            "retry_success_rate": (round(retried_ok / len(retried), 4)
                                   if retried else None),
            "hedges": snap["hedges"],
            "hedge_win_rate": (round(snap["hedges_won"] / snap["hedges"], 4)
                               if snap["hedges"] else None),
            "failovers": snap["failovers"],
            "kill_at_request": kill_at,
            "killed_at_s": (round(killed_at_s, 3)
                            if killed_at_s is not None else None),
            "deadline_s": args.avail_deadline_s,
            "interarrival_ms": args.avail_interarrival_ms,
            "retry_budget": args.retry_budget,
            "hedge_ms": args.hedge_ms,
            "wall_s": round(wall, 3),
            "seed": args.seed,
            "router": snap,
        },
    }
    print(json.dumps(row))
    # A dropped request (accepted, never reached a terminal status) is
    # the one unacceptable outcome — the row reports availability, the
    # exit code guards delivery.
    return 0 if dropped == 0 else 1


def run_spec(args) -> int:
    """Plain vs speculative decode on one prompt set (see module
    docstring).  The worst-case leg runs the ADAPTIVE controller with a
    short window so the run demonstrates the bound it gates on: shrink
    to k=1, then speculation OFF with amortized probes."""
    import jax
    import numpy as np

    from tpucfn.serve import Server
    from tpucfn.serve.engine import ServeEngine, demo_llama_engine
    from tpucfn.serve.scheduler import prefill_bucket
    from tpucfn.serve.spec import SpecDecoder, SpecKController

    print(f"# backend={jax.default_backend()} spec drill "
          f"preset={args.preset} k={args.spec_k} "
          f"requests={args.spec_requests} max_new={args.spec_max_new}",
          file=sys.stderr)
    cfg, target_plain = demo_llama_engine(
        args.preset, seed=args.seed, max_batch=args.max_batch,
        cache_len=args.cache_len, prefill_width=args.max_prefill_batch)
    params = target_plain.params

    def eng(p=None, seed=None):
        if p is not None:
            return ServeEngine.from_llama(
                cfg, p, max_batch=args.max_batch, cache_len=args.cache_len,
                prefill_width=args.max_prefill_batch)
        _, e = demo_llama_engine(
            "nano", seed=seed, max_batch=args.max_batch,
            cache_len=args.cache_len, prefill_width=args.max_prefill_batch)
        return e

    # High-acceptance leg: self-draft (identical weights — the draft
    # always agrees, the synthetic upper bound real distilled drafts
    # approach).  Worst-case leg: a nano draft with DIVERGENT weights
    # (different init seed) — acceptance ~0 on random-init models.
    spec_hi = SpecDecoder(eng(params), eng(params), k=args.spec_k)
    spec_lo = SpecDecoder(
        eng(params), eng(seed=args.seed + 1),
        controller=SpecKController(k=args.spec_k, window=4,
                                   probe_every=64))

    rs = np.random.RandomState(args.seed)
    prompts = [rs.randint(0, cfg.vocab_size,
                          rs.randint(args.prompt_len_lo,
                                     args.prompt_len_hi + 1)).tolist()
               for _ in range(args.spec_requests)]

    def leg(engine, fresh_controller=None):
        # compile warmup on the engine pair (buckets, decode, verify
        # widths, rollback), excluded from the timed pass.
        warm = Server(engine, num_blocks=args.num_blocks,
                      block_size=args.block_size, prefix_cache=False,
                      max_prefill_batch=args.max_prefill_batch)
        for b in sorted({prefill_bucket(len(q), args.cache_len)
                         for q in prompts}):
            warm.submit([1] * min(b, args.cache_len - args.spec_max_new),
                        max_new_tokens=min(args.spec_max_new, 24))
        warm.run_until_idle()
        if fresh_controller is not None:
            # The warmup also ADAPTED the controller (an adversarial
            # warmup leaves it already off).  Reset it so the timed
            # pass pays the full shrink-to-off transient — the gate
            # bounds the controller's whole trajectory, not just its
            # steady state.
            engine.controller = fresh_controller()
        server = Server(engine, num_blocks=args.num_blocks,
                        block_size=args.block_size, prefix_cache=False,
                        max_prefill_batch=args.max_prefill_batch)
        t0 = time.perf_counter()
        reqs = [server.submit(q, max_new_tokens=args.spec_max_new)
                for q in prompts]
        server.run_until_idle()
        wall = time.perf_counter() - t0
        outs = [r.result(timeout=0) for r in reqs]
        tpots = [(r.t_done - r.t_first_token) / (len(r.tokens) - 1)
                 for r in reqs if r.tokens and len(r.tokens) > 1]
        snap = server.metrics.snapshot()
        assert server.kv.allocator.num_used == 0, "KV blocks leaked"
        return outs, {
            "wall_s": round(wall, 3),
            "tokens_per_target_step": snap["tokens_per_target_step"],
            "acceptance_rate": snap["spec_acceptance_rate"],
            "spec_proposed": snap["spec_proposed"],
            "spec_accepted": snap["spec_accepted"],
            "decode_rounds": snap["decode_rounds"],
            "spec_rounds": snap["spec_rounds"],
            "tpot_mean_s": (round(sum(tpots) / len(tpots), 6)
                            if tpots else None),
            "tokens_per_sec": round(snap["generated_tokens"] / wall, 3),
        }

    ref, plain = leg(target_plain)
    out_hi, hi = leg(spec_hi)
    out_lo, lo = leg(
        spec_lo,
        fresh_controller=lambda: SpecKController(
            k=args.spec_k, window=4, probe_every=64))
    hi["controller_k_final"] = spec_hi.controller.k
    lo["controller_k_final"] = spec_lo.controller.k

    identical = (out_hi == ref) and (out_lo == ref)
    tps_gain = (hi["tokens_per_target_step"] or 0.0) \
        / max(plain["tokens_per_target_step"] or 1.0, 1e-9)
    tpot_ratio = (lo["tpot_mean_s"] / plain["tpot_mean_s"]
                  if lo["tpot_mean_s"] and plain["tpot_mean_s"] else None)
    gates = {
        "bit_identical": identical,
        "tokens_per_target_step_gain": round(tps_gain, 3),
        "tokens_per_target_step_gate": tps_gain >= 1.5,
        "worst_case_tpot_ratio": (round(tpot_ratio, 3)
                                  if tpot_ratio is not None else None),
        "worst_case_tpot_gate": (tpot_ratio is not None
                                 and tpot_ratio <= 1.3),
    }
    row = {
        "metric": "serve_spec_tokens_per_target_step",
        "value": hi["tokens_per_target_step"],
        "unit": "decode tokens per target dispatch per slot "
                "(high-acceptance self-draft leg)",
        "vs_baseline": 0.0,
        "detail": {
            "baseline_note": "reference harness was training-only; no "
                             "published speculative-decode number exists",
            "backend": jax.default_backend(),
            "preset": args.preset,
            "draft": {"high_acceptance": "self",
                      "worst_case": "nano (divergent init)"},
            "spec_k": args.spec_k,
            "requests": args.spec_requests,
            "max_new": args.spec_max_new,
            "max_batch": args.max_batch,
            "plain": plain,
            "spec_high_acceptance": hi,
            "spec_worst_case": lo,
            "gates": gates,
            "seed": args.seed,
        },
    }
    print(json.dumps(row))
    ok = (identical and gates["tokens_per_target_step_gate"]
          and gates["worst_case_tpot_gate"])
    return 0 if ok else 1


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", choices=["tiny", "llama3-1b", "llama3-8b"],
                   default="tiny")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--prompt-len-lo", type=int, default=8)
    p.add_argument("--prompt-len-hi", type=int, default=96)
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--cache-len", type=int, default=256)
    p.add_argument("--num-blocks", type=int, default=512)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--shared-prefix-len", type=int, default=64,
                   help="common system-prompt length of the shared-prefix "
                        "workload")
    p.add_argument("--max-prefill-batch", type=int, default=4)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--availability", action="store_true",
                   help="run the 2-replica open-loop availability drill "
                        "(replica killed mid-trace) instead of the "
                        "throughput workloads")
    p.add_argument("--avail-requests", type=int, default=24)
    p.add_argument("--avail-deadline-s", type=float, default=15.0)
    p.add_argument("--avail-interarrival-ms", type=float, default=30.0,
                   help="mean of the seeded exponential inter-arrival "
                        "trace")
    p.add_argument("--retry-budget", type=int, default=2)
    p.add_argument("--hedge-ms", type=float, default=250.0,
                   help="hedge delay floor for the availability drill "
                        "(0 disables hedging)")
    p.add_argument("--spec", action="store_true",
                   help="run the speculative-decoding drill (plain vs "
                        "self-draft vs adversarial nano draft) instead "
                        "of the throughput workloads")
    p.add_argument("--spec-k", type=int, default=4)
    p.add_argument("--spec-requests", type=int, default=8)
    p.add_argument("--spec-max-new", type=int, default=96,
                   help="decode length of the spec drill (long enough "
                        "for the adaptive controller to reach its "
                        "steady state on the adversarial leg)")
    args = p.parse_args()

    if args.availability:
        return run_availability(args)
    if args.spec:
        return run_spec(args)

    import jax
    import numpy as np

    from tpucfn.serve import Server
    from tpucfn.serve.engine import demo_llama_engine
    from tpucfn.serve.scheduler import prefill_bucket

    print(f"# backend={jax.default_backend()} preset={args.preset} "
          f"requests={args.requests}", file=sys.stderr)
    cfg, engine = demo_llama_engine(args.preset, seed=args.seed,
                                    max_batch=args.max_batch,
                                    cache_len=args.cache_len,
                                    prefill_width=args.max_prefill_batch)

    rs = np.random.RandomState(args.seed)
    mixed = [rs.randint(0, cfg.vocab_size,
                        rs.randint(args.prompt_len_lo,
                                   args.prompt_len_hi + 1)).tolist()
             for _ in range(args.requests)]
    # Shared-prefix workload: one system prompt, per-request tails sized
    # to land in ONE suffix bucket (tail in (block_size, 2*block_size])
    # so batched-prefill call counts are deterministic.
    sys_prompt = rs.randint(0, cfg.vocab_size,
                            args.shared_prefix_len).tolist()
    shared = [sys_prompt + rs.randint(
        0, cfg.vocab_size,
        rs.randint(args.block_size + 1, 2 * args.block_size + 1)).tolist()
        for _ in range(args.requests)]

    # -- compile warmup (excluded from every timed window) -----------------
    # prefix_cache OFF here: the warm prompts all share a [1]*n prefix,
    # and a hit would prefill a short suffix in a SMALLER bucket —
    # leaving the large buckets uncompiled for the timed phases.
    warm = Server(engine, num_blocks=args.num_blocks,
                  block_size=args.block_size, prefix_cache=False,
                  max_prefill_batch=args.max_prefill_batch)
    for b in sorted({prefill_bucket(len(q), args.cache_len)
                     for q in mixed}):
        warm.submit([1] * min(b, args.cache_len - 2), max_new_tokens=2)
    warm.run_until_idle()
    # the shared-prefix phase's programs: full bucket, suffix bucket,
    # copy_prefix (two identical-prefix requests back to back).
    _run_workload(engine, args, shared[: 2 * args.max_prefill_batch],
                  prefix_cache=True,
                  max_prefill_batch=args.max_prefill_batch, max_new=2)

    # -- timed: mixed headline ---------------------------------------------
    head = _run_workload(engine, args, mixed, prefix_cache=True,
                         max_prefill_batch=args.max_prefill_batch,
                         max_new=args.max_new)
    # -- timed: shared-prefix, cache off vs on, same run -------------------
    off = _run_workload(engine, args, shared, prefix_cache=False,
                        max_prefill_batch=1, max_new=args.max_new)
    on = _run_workload(engine, args, shared, prefix_cache=True,
                       max_prefill_batch=args.max_prefill_batch,
                       max_new=args.max_new)
    reduction = (off["prefilled_tokens_per_request"]
                 / max(on["prefilled_tokens_per_request"], 1e-9))

    strip = lambda d: {k: v for k, v in d.items() if k != "snapshot"}  # noqa: E731
    row = {
        "metric": "serve_tokens_per_sec",
        "value": head["tokens_per_sec"],
        "unit": "generated tokens/sec",
        "vs_baseline": 0.0,
        "detail": {
            "baseline_note": "reference harness was training-only; no "
                             "published serving number exists",
            "backend": jax.default_backend(),
            "preset": args.preset,
            "requests": args.requests,
            "failed": head["failed"],
            "wall_s": head["wall_s"],
            "max_batch": args.max_batch,
            "cache_len": args.cache_len,
            "block_size": args.block_size,
            "num_blocks": args.num_blocks,
            "max_new": args.max_new,
            "max_prefill_batch": args.max_prefill_batch,
            "ttft_s": head["snapshot"]["ttft_s"],
            "request_latency_s": head["snapshot"]["request_latency_s"],
            "preemptions": head["snapshot"]["preemptions"],
            "kv_blocks_high_water": head["kv_blocks_high_water"],
            "kv_blocks_leaked": head["kv_blocks_leaked"],
            # The full ServingMetrics snapshot rides on every row so a
            # perf regression carries its own latency decomposition
            # (queue depth, occupancy, token counts) instead of just the
            # headline number (ISSUE 2 satellite).
            "serving_metrics": head["snapshot"],
            # The serve_slo_* snapshot (ISSUE 5): TTFT/TPOT objective
            # targets, violation counts, and rolling-window burn rates
            # for the headline workload.
            "serve_slo": head["slo"],
            # ISSUE 3 acceptance: prefix caching's prefilled-token
            # reduction and batched prefill's call ceiling, cache off vs
            # on over identical prompts in the same run.
            "shared_prefix": {
                "prefix_len": args.shared_prefix_len,
                "requests": args.requests,
                "max_prefill_batch": args.max_prefill_batch,
                "prefill_calls_ceiling": math.ceil(
                    args.requests / args.max_prefill_batch),
                "off": strip(off),
                "on": strip(on),
                "prefilled_tokens_reduction": round(reduction, 3),
            },
        },
    }
    print(json.dumps(row))
    leaked = (head["kv_blocks_leaked"] or off["kv_blocks_leaked"]
              or on["kv_blocks_leaked"])
    failed = head["failed"] or off["failed"] or on["failed"]
    return 0 if not failed and not leaked else 1


if __name__ == "__main__":
    sys.exit(main())
