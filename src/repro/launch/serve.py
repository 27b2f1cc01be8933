"""Serving launcher: the intelligent-router gateway over the calibrated
simulator (--backend py|vec|jax) or real JAX engine replicas
(--backend engine).  Every run goes through ``serving.gateway.Gateway``.

  # open-loop multi-tenant stream, pluggable policy, learned length
  # predictor in the loop, rolling SLO metrics; --policy rl trains a
  # quick agent on the simulator unless --checkpoint is given
  PYTHONPATH=src python -m repro.launch.serve \
      --policy rl|mixing|jsq|rr --pattern bursty --queue-cap 64

  # same, with lifecycle tracing + metrics export (serving.obs):
  # trace.json opens in Perfetto, metrics.json/.prom is the scrape
  PYTHONPATH=src python -m repro.launch.serve \
      --trace trace.json --metrics-out metrics.json

  # real engines: qwen3-0.6b at published widths, one replica per
  # accelerator (TPU); --reduced runs the tiny preset on CPU
  PYTHONPATH=src python -m repro.launch.serve \
      --backend engine --instances 1 --requests 12
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --backend engine --reduced --requests 12

  # chaos drill: seeded crash/straggler schedule with gateway failover
  # (bounded-retry re-admission, circuit breaker, hedged re-dispatch)
  PYTHONPATH=src python -m repro.launch.serve \
      --chaos-seed 7 --chaos-crashes 1 --chaos-stragglers 1 \
      --failover --hedge-after 4.0

  # ONLINE continual learning: the gateway trains its own router on the
  # live stream (training.online) with the r_mixing safe-fallback
  # guardrail; --drift serves the nonstationary mix-flip scenario where
  # a frozen policy degrades.  --checkpoint warm-starts the learner.
  PYTHONPATH=src python -m repro.launch.serve \
      --online --guardrail 0.12 --drift --checkpoint ckpt_dir

  # calibrate a HardwareProfile from the real engine (core.calibrate):
  # sweep + fit, print diagnostics, write a committable JSON artifact.
  # --min-r2 makes a loose fit a non-zero exit (CI calibration-smoke).
  PYTHONPATH=src python -m repro.launch.serve --calibrate \
      --arch qwen3-0.6b --profile-json profile.json --min-r2 0.95

  # serve on a previously calibrated profile instead of the V100 default
  PYTHONPATH=src python -m repro.launch.serve --profile-json profile.json
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import sys
from concurrent.futures import ThreadPoolExecutor

# calibration times jitted kernels: single-threaded XLA keeps the sweep
# linear (multi-threaded CPU XLA switches parallelization strategy with
# size, which reads as piecewise cost steps).  Must be set before jax
# imports, so it is keyed off argv rather than the parsed args.
if "--calibrate" in sys.argv:
    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_cpu_multi_thread_eigen=false "
        "intra_op_parallelism_threads=1")

import jax
import numpy as np

from repro.configs import get_config
from repro.core import calibrate as cal
from repro.core import rl_router as rl
from repro.core import workload as wl
from repro.core.predictor import quick_bucket_predictor
from repro.core.profiles import V100_LLAMA2_7B, tpu_v5e_profile
from repro.core.workload import generate, to_requests
from repro.models import params as params_lib
from repro.serving.engine import LLMInstance, next_ids, place_first
from repro.serving.gateway import (EngineClusterAdapter, Gateway,
                                   GatewayConfig, MicroBatchPredictor)
from repro.serving.metrics import format_snapshot
from repro.serving.policies import (RLPolicy, make_gateway_policy,
                                    restore_rl_policy)
from repro.serving.request import Request
from repro.serving.scheduler import get_scheduler


# the checkout this module lives in (src/repro/launch/serve.py)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

# engine-path request draws, [lo, hi) token counts
ENGINE_PROMPTS = (10, 80)
ENGINE_DECODES = (5, 60)


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache where JAX_COMPILATION_CACHE_DIR
    says (JAX reads that itself), else at ``<checkout>/.jax_cache``.
    Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_engine_fleet(arch: str, n_instances: int, *,
                       reduced: bool = False, n_slots: int = 8,
                       cache_len: int = 2048, seed: int = 0,
                       profile=None, scheduler: str = "fcfs",
                       prefix_cache_tokens: int = 0,
                       prefix_block: int = 32):
    """``n_instances`` engine replicas of ``arch`` sharing params drawn
    from ``seed``.

    By default the model runs at its published config (bf16) with the
    Pallas kernels; ``reduced`` gives the tiny float32 jnp preset CPU
    callers use.  Replica i lives on device i of ``jax.devices()``,
    round robin.  ``profile`` drives the virtual clock and the router's
    scores; it defaults to the analytic one-chip v5e profile of the
    published model, and is clamped to the KV the replicas hold."""
    published = get_config(arch)
    cfg = (published.reduced() if reduced
           else dataclasses.replace(published, use_pallas=True))
    params = params_lib.init_params(jax.random.PRNGKey(seed), cfg)
    if profile is None:
        profile = tpu_v5e_profile(published.count_params(), tp=1,
                                  name=f"v5e-{arch}")
    profile = dataclasses.replace(
        profile, capacity_tokens=min(profile.capacity_tokens,
                                     n_slots * cache_len))
    devices = jax.devices()
    return [LLMInstance(cfg, params, profile, get_scheduler(scheduler),
                        n_slots=n_slots, cache_len=cache_len,
                        instance_id=i,
                        prefix_cache_tokens=prefix_cache_tokens,
                        prefix_block=prefix_block,
                        device=devices[i % len(devices)])
            for i in range(n_instances)]


def warm_fleet(engines, prompt_lengths) -> None:
    """Run each replica's prefill at every prompt length, its slot
    insert, first-token placement, decode step and next-id argmax once,
    compiling in parallel threads, so nothing compiles while serving.
    Slots stay empty."""
    lengths = sorted(set(int(n) for n in prompt_lengths))

    def prefill(job):
        e, n = job
        toks = jax.device_put(np.zeros((1, n), np.int32), e.device)
        return jax.block_until_ready(e.prefill_fn(e.params, toks))

    def insert_and_decode(e):
        logits, small = prefill((e, lengths[0]))
        e.cache = e.insert_fn(e.cache, small, 0)
        e.tokens = place_first(e.tokens, logits, 0)
        logits, e.cache = e.decode_fn(e.params, e.cache, e.tokens)[:2]
        e.tokens = next_ids(logits)
        jax.block_until_ready((e.cache, e.tokens))

    with ThreadPoolExecutor(os.cpu_count()) as ex:
        list(ex.map(prefill, [(e, n) for e in engines for n in lengths]))
        list(ex.map(insert_and_decode, engines))


def draw_engine_requests(n: int, vocab: int, seed: int) -> list:
    """The engine path's request stream: lengths from ENGINE_PROMPTS /
    ENGINE_DECODES and prompt ids from ``seed``, 50 ms apart."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.integers(*ENGINE_PROMPTS))
        reqs.append(Request(prompt_tokens=p,
                            decode_tokens=int(rng.integers(*ENGINE_DECODES)),
                            arrival=0.05 * i, tenant="engine",
                            tokens=rng.integers(0, vocab, p).tolist()))
    return reqs


def _router_cfg(args) -> rl.RouterConfig:
    # the online learner gets the health features: under chaos they
    # carry the straggler/degradation signal it adapts to
    return rl.RouterConfig(variant="guided", n_instances=args.instances,
                           q_arch="decomposed", seed=0,
                           explore_episodes=max(args.train_episodes - 3,
                                                1),
                           scheduler=args.scheduler,
                           chunked_prefill=args.chunked_prefill,
                           prefix_cache_tokens=args.prefix_cache,
                           prefix_block=args.prefix_block,
                           cache_weight=(0.5 if args.prefix_cache
                                         else 0.0),
                           include_cache_features=bool(
                               args.prefix_cache),
                           include_health_features=args.online)


def _train_quick_agent(args, cfg: rl.RouterConfig, profile):
    out = rl.train(cfg, profile,
                   lambda ep: to_requests(generate(args.requests, seed=ep),
                                          rate=args.rate, seed=ep + 50),
                   n_episodes=args.train_episodes)
    return out["agent"]


def _base_profile(args):
    """The serving profile: a calibrated JSON artifact when given,
    else the paper's V100 calibration."""
    if args.profile_json and not args.calibrate:
        return cal.load_profile(args.profile_json)
    return V100_LLAMA2_7B


def run_calibrate(args) -> int:
    """--calibrate: sweep the reduced engine for --arch, fit a profile,
    print diagnostics, optionally write --profile-json.  Non-zero exit
    when the fit misses --min-r2 or the gradient sanity ordering (the
    CI calibration-smoke gate)."""
    cfg = get_config(args.arch).reduced()
    params = params_lib.init_params(jax.random.PRNGKey(0), cfg)
    res = cal.calibrate(cfg, params)
    print(cal.format_result(res))
    if args.profile_json:
        res.save(args.profile_json)
        print(f"wrote {args.profile_json}")
    failures = []
    if res.prefill_fit.r2 < args.min_r2:
        failures.append(f"prefill R^2 {res.prefill_fit.r2:.4f} "
                        f"< {args.min_r2}")
    if res.decode_fit.r2 < args.min_r2:
        failures.append(f"decode R^2 {res.decode_fit.r2:.4f} "
                        f"< {args.min_r2}")
    if not res.ok:
        failures.append("gradient sanity (grad1 > grad2 > 0) failed")
    for f in failures:
        print(f"CALIBRATION GATE: {f}")
    return 1 if failures else 0


def _engines(args) -> list:
    """The CLI's engine fleet: published widths on the accelerator, or
    with --reduced the tiny CPU preset (4 slots, a cache that fits the
    longest draw, a 400-token KV budget on the base profile)."""
    if args.reduced:
        base = _base_profile(args)
        sizes = dict(n_slots=4,
                     cache_len=ENGINE_PROMPTS[1] + ENGINE_DECODES[1],
                     profile=dataclasses.replace(
                         base, capacity_tokens=min(base.capacity_tokens,
                                                   400)))
    else:
        sizes = dict(profile=(cal.load_profile(args.profile_json)
                              if args.profile_json else None))
    return build_engine_fleet(
        args.arch, args.instances, reduced=args.reduced, seed=args.seed,
        scheduler=args.scheduler, prefix_cache_tokens=args.prefix_cache,
        prefix_block=args.prefix_block, **sizes)


def _chaos_schedule(args):
    """--chaos-seed: build the seeded FaultSchedule for this run."""
    if args.chaos_seed is None:
        return None
    from repro.serving.chaos import FaultSchedule
    horizon = args.requests / max(args.rate, 1e-9)
    return FaultSchedule.random(
        seed=args.chaos_seed, m=args.instances, horizon=horizon,
        n_crashes=args.chaos_crashes,
        n_stragglers=args.chaos_stragglers,
        n_bursts=args.chaos_bursts)


def serve(args):
    """Online gateway over the simulator (default) or real engines."""
    cfg = _router_cfg(args)
    chaos = _chaos_schedule(args)
    gcfg = GatewayConfig(queue_cap=args.queue_cap, on_full=args.on_full,
                         scheduler=args.scheduler,
                         chunked_prefill=args.chunked_prefill,
                         backend=args.backend,
                         default_deadline_s=args.deadline,
                         prefix_cache_tokens=args.prefix_cache,
                         prefix_block=args.prefix_block,
                         attribution=bool(args.metrics_out),
                         chaos=chaos, failover=args.failover,
                         max_retries=args.max_retries,
                         hedge_after_s=args.hedge_after)
    recorder = None
    trainer = None
    if args.trace:
        from repro.serving import trace as trace_lib
        recorder = trace_lib.TraceRecorder(sample=args.trace_sample)
    if args.backend == "engine":
        if args.online:
            raise SystemExit("--online needs a simulator backend "
                             "(py/vec/jax): the engine adapter fires no "
                             "decode events for the backlog reward)")
        if args.policy == "rl" and not args.checkpoint:
            raise SystemExit("--backend engine --policy rl needs "
                             "--checkpoint (no simulator to train on)")
        # real engines: seeded random prompts, oracle-free routing (no
        # content for the predictor)
        engines = _engines(args)
        cluster = EngineClusterAdapter(engines)
        if args.policy == "rl":
            policy = restore_rl_policy(cfg, args.checkpoint,
                                       m=args.instances)
        else:
            policy = make_gateway_policy(args.policy, cfg)
        gw = Gateway(gcfg, None, policy, cluster=cluster,
                     trace=recorder)
        stats = gw.run(draw_engine_requests(
            args.requests, engines[0].cfg.vocab_size, args.seed))
    else:
        base = _base_profile(args)
        profiles = (base,) * args.instances
        sessions = (wl.SessionConfig(block=args.prefix_block)
                    if args.sessions else None)
        if args.drift:
            scn = wl.make_drift_scenario(seed=7,
                                         n_requests=args.requests,
                                         rate=args.rate,
                                         pattern=args.pattern,
                                         profiles=profiles)
            if chaos is None and scn.meta["chaos"] is not None:
                chaos = scn.meta["chaos"]
                gcfg = dataclasses.replace(gcfg, chaos=chaos,
                                           failover=True)
        else:
            scn = wl.make_tenant_scenario(seed=7,
                                          n_requests=args.requests,
                                          rate=args.rate,
                                          pattern=args.pattern,
                                          profiles=profiles,
                                          sessions=sessions)
        length = MicroBatchPredictor(quick_bucket_predictor(
            base, n_train=2000, epochs=2))
        if args.online:
            from repro.training.online import OnlineConfig, OnlineTrainer
            ocfg = OnlineConfig(eps=args.online_eps,
                                guard=args.guardrail > 0,
                                guard_regret=args.guardrail,
                                warm_start=args.checkpoint,
                                checkpoint_dir=args.save_learner,
                                checkpoint_every=(500 if args.save_learner
                                                  else 0))
            trainer = OnlineTrainer(cfg, ocfg, m=args.instances)
            policy = trainer.policy
        elif args.policy == "rl":
            if args.checkpoint:
                policy = restore_rl_policy(cfg, args.checkpoint,
                                           m=args.instances)
            else:
                policy = RLPolicy(
                    _train_quick_agent(args, cfg, base), cfg)
        else:
            policy = make_gateway_policy(args.policy, cfg)
        gw = Gateway(gcfg, profiles, policy, length=length,
                     trace=recorder)
        if chaos is not None and chaos.bursts:
            from repro.serving.chaos import inject_bursts
            reqs = inject_bursts(scn.requests, chaos,
                                 seed=args.chaos_seed)
            samples = list(scn.samples) + [None] * (
                len(reqs) - len(scn.requests))
            stats = gw.run(reqs, samples=samples)
        else:
            stats = gw.run(scn)
    print(f"policy={stats['policy']} served n={stats['n']} "
          f"admitted={stats['admitted']} shed={stats['shed']} "
          f"preemptions={stats['preemptions']}")
    if chaos is not None or args.failover:
        print(f"chaos: orphaned={stats['orphaned']} "
              f"retried={stats['retried']} hedged={stats['hedged']} "
              f"breaker_trips={stats.get('breaker_trips', 0)}")
    if trainer is not None:
        t = trainer.telemetry()
        print(f"online: decisions={int(t['decisions'])} "
              f"transitions={int(t['transitions'])} "
              f"learner_steps={trainer.agent.steps} "
              f"publishes={int(t['publishes'])} "
              f"explored={int(t['explored'])} "
              f"fallback_entries={int(t['fallback_entries'])} "
              f"mode={t['mode']}")
    print(format_snapshot(stats["snapshot"]))
    if args.trace or args.metrics_out:
        from repro.serving import obs
        if args.trace:
            doc = obs.write_trace(recorder, args.trace,
                                  title=f"gateway-{stats['policy']}")
            print(f"wrote {args.trace} "
                  f"({len(doc['traceEvents'])} trace events, "
                  f"{recorder.dropped} dropped)")
        if args.metrics_out:
            reg = obs.MetricsRegistry()
            reg.ingest_snapshot(stats["snapshot"])
            telemetry = getattr(getattr(gw.policy, "agent", None),
                                "telemetry", None)
            if telemetry is not None:
                reg.ingest_rl(telemetry())
            reg.save(args.metrics_out)
            print(f"wrote {args.metrics_out} ({len(reg)} metrics)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=("py", "vec", "jax", "engine"),
                    default="py",
                    help="cluster behind the gateway: a simulator "
                    "stepper from the core.backends registry "
                    "(py/vec/jax) or real engine replicas (engine)")
    ap.add_argument("--reduced", action="store_true",
                    help="engine replicas at the tiny CPU preset "
                    "(ModelConfig.reduced) instead of the published "
                    "widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine params and prompt ids")
    ap.add_argument("--policy", default="mixing",
                    choices=("rl", "mixing", "mixing+cache", "jsq",
                             "rr", "sticky"),
                    help="routing policy")
    ap.add_argument("--pattern", default="bursty",
                    choices=("poisson", "bursty", "diurnal"))
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="admission queue bound (0 = unbounded)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="client timeout in seconds (deferred requests "
                    "past it are cancelled)")
    ap.add_argument("--on-full", default="shed",
                    choices=("shed", "defer"))
    ap.add_argument("--prefix-cache", type=int, default=0,
                    help="per-instance prefix/KV cache budget in "
                    "tokens (0 = cache model off); enables the "
                    "cache-affinity policies and RL state feature")
    ap.add_argument("--prefix-block", type=int, default=32,
                    help="prefix-cache hash-block size in tokens")
    ap.add_argument("--sessions", action="store_true",
                    help="multi-turn conversation workload "
                    "(follow-up prompts extend prior turns; tenants "
                    "share system prompts) instead of independent "
                    "queries")
    ap.add_argument("--checkpoint", default=None,
                    help="router checkpoint dir for --policy rl (and "
                    "the warm-start source for --online)")
    ap.add_argument("--online", action="store_true",
                    help="continual learning on the live "
                    "stream (training.online) -- the router trains on "
                    "its own transitions between arrival windows and "
                    "hot-swaps refreshed weights without pausing "
                    "admission; implies the RL policy with health "
                    "features")
    ap.add_argument("--guardrail", type=float, default=0.12,
                    metavar="REGRET",
                    help="--online safe fallback: when the Q-head's "
                    "mean r_mixing regret over the guard window "
                    "exceeds this, route by r_mixing for a cooldown "
                    "while learning continues (0 = guardrail off)")
    ap.add_argument("--online-eps", type=float, default=0.05,
                    help="--online guided exploration rate (softmax "
                    "over the r_mixing guidance bonus)")
    ap.add_argument("--drift", action="store_true",
                    help="serve the nonstationary drift "
                    "scenario (mid-stream workload-mix flip + tenant "
                    "churn + straggler/crash chaos) instead of the "
                    "stationary tenant mix")
    ap.add_argument("--save-learner", default=None, metavar="DIR",
                    help="--online: periodically checkpoint the FULL "
                    "learner state (Q + target + optimizer + replay) "
                    "here for exact mid-stream resume")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a seeded FaultSchedule "
                    "(serving.chaos) of crashes / stragglers / tenant "
                    "bursts into the run")
    ap.add_argument("--chaos-crashes", type=int, default=1,
                    help="crash+restart events in the schedule")
    ap.add_argument("--chaos-stragglers", type=int, default=1,
                    help="straggler slowdown windows in the schedule")
    ap.add_argument("--chaos-bursts", type=int, default=0,
                    help="correlated tenant-burst windows")
    ap.add_argument("--failover", action="store_true",
                    help="gateway failover: crash orphans re-enter "
                    "admission with bounded retries + backoff; health "
                    "tracker / circuit breaker filters candidates")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="failover retry budget per request")
    ap.add_argument("--hedge-after", type=float, default=None,
                    metavar="SECONDS",
                    help="hedged re-dispatch: withdraw a routed "
                    "request still tokenless after this long and "
                    "re-route it (None = off)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record request lifecycle spans and "
                    "write a Chrome trace-event JSON (load in Perfetto "
                    "/ chrome://tracing)")
    ap.add_argument("--trace-sample", type=float, default=1.0,
                    help="head-sampling fraction of requests traced")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry (SLO "
                    "snapshot + decision attribution + RL telemetry) "
                    "as JSON, or Prometheus text if PATH ends in "
                    ".prom")
    ap.add_argument("--calibrate", action="store_true",
                    help="sweep the reduced --arch engine and fit a "
                    "HardwareProfile (core.calibrate); prints fit "
                    "diagnostics and exits")
    ap.add_argument("--profile-json", default=None,
                    help="with --calibrate: write the calibrated "
                    "profile artifact here; otherwise: serve with the "
                    "profile loaded from this JSON instead of the "
                    "default V100 calibration")
    ap.add_argument("--min-r2", type=float, default=0.0,
                    help="with --calibrate: exit non-zero unless both "
                    "fits reach this R^2 (CI gate)")
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--scheduler", default="fcfs")
    ap.add_argument("--chunked-prefill", type=int, default=0)
    ap.add_argument("--train-episodes", type=int, default=8)
    args = ap.parse_args()
    use_compile_cache()
    if args.calibrate:
        sys.exit(run_calibrate(args))
    serve(args)


if __name__ == "__main__":
    main()
