"""One run of one cell of the chip benchmark.

  python benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``) is a configuration under a traffic mix.  The
run, in order:

  1. needs a TPU with the cell's chips (no fallback);
  2. builds the configuration's replicas with the program's
     ``build_engine_fleet`` (weights from ``--seed`` on the device, the
     profile pinned by the configuration file) and checks that the
     program runs the sizes the file states;
  3. warms the prompt lengths the cell's stream holds, insert and
     decode, from the persistent compile cache ``use_compile_cache``;
  4. serves the stream drawn from ``--seed`` through ``Gateway.run``
     over ``EngineClusterAdapter``, with the configuration's policy;
     arrivals are due on the gateway's clock, so the sequence of
     admissions, routes and engine steps depends only on the seed, the
     traffic and the configuration;
  5. opens the window once the gateway's clock passes the traffic's
     ``warmup_s`` and closes it ``--seconds`` of wall time later;
  6. with ``--trace 1`` traces the first TRACE_SECONDS of the window;
  7. reads the device's peak memory, frees the program's state and
     checks the served requests against the plain reference (check.py).

Set-up (``setup_s``) is process start to window start.  The last line
of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit.  The same numbers end
stderr.  Any fault exits non-zero without a result line.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse                                    # noqa: E402
import gc                                          # noqa: E402
import glob                                        # noqa: E402
import json                                        # noqa: E402
import os                                          # noqa: E402
import shutil                                      # noqa: E402
import sys                                         # noqa: E402
import tempfile                                    # noqa: E402
from types import SimpleNamespace                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import check                                       # noqa: E402
import endtoend                                    # noqa: E402
import replay                                      # noqa: E402
import spec                                        # noqa: E402
import devtrace as trace_lib                       # noqa: E402
import traffic as traffic_lib                      # noqa: E402

TRACE_SECONDS = 5.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

class RunError(RuntimeError):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_program():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise RunError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


class Compiles:
    """Counts executables the process builds (``built``), not counting
    those it loads from the persistent cache."""

    def __init__(self):
        self.n = self.hits = 0

    @property
    def built(self) -> int:
        return self.n - self.hits

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1

    def hit(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1


class TraceWindow:
    """Starts the profiler once the window is open and stops it
    TRACE_SECONDS later, between two advances."""

    def __init__(self, seconds: float):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.seconds = seconds
        self.first = self.last = None      # advance indices traced
        self.t_start = None

    def __call__(self, rec, t_end):
        import jax
        k = len(rec.adv_end) - 1
        if self.t_start is None:
            if rec.t_open is not None:
                # no Python function tracing: it slows the host several
                # fold; TraceMe spans (JAX's and the harness's) remain
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                self.t_start = time.perf_counter()
                self.first = k + 1
        elif self.last is None and t_end >= self.t_start + self.seconds:
            self.last = k
            jax.profiler.stop_trace()

    def stop(self, rec):
        if self.t_start is not None and self.last is None:
            import jax
            self.last = len(rec.adv_end) - 1
            jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RunError("the profiler wrote no trace")
        return found[0]


def decode_steps(rec, served_by, advances) -> dict:
    """The decode steps of ``advances``: (replica, virtual time) -> the
    context of each active row (prompt + tokens before + 1), grouped by
    the program's per-token stamps; and the prefills: replica -> prompt
    lengths."""
    steps, prefills, step_adv = {}, {}, {}
    keep = set(advances)
    for rid, stamps in rec.token_adv.items():
        req = rec.requests[rid]
        for j, k in enumerate(stamps):
            if k not in keep:
                continue
            key = (served_by[rid], req.token_times[j])
            steps.setdefault(key, []).append(req.prompt_tokens + j + 1)
            step_adv[key] = k
            if j == 0:
                prefills.setdefault(served_by[rid], []).append(
                    req.prompt_tokens)
    return {"steps": steps, "prefills": prefills, "step_adv": step_adv}


def trace_context(tracer, rec, policy, served_by, cfg, peaks, e2e,
                  used) -> SimpleNamespace:
    """What the per-layer readers read (``metrics/<name>.py``): the
    traced window's device trace and host spans, the recorder and the
    router's stamps, the decode steps and prefills of the traced
    advances, the configuration, the device's peaks and the end-to-end
    numbers."""
    advances = list(range(tracer.first, tracer.last + 1))
    return SimpleNamespace(
        trace=trace_lib.read(tracer.xplane()), rec=rec, policy=policy,
        work=decode_steps(rec, served_by, advances), model=cfg,
        peaks=peaks, e2e=e2e, devices=[f"TPU:{d.id}" for d in used],
        tracer=tracer)


def breakdown(tr) -> dict:
    """The ten operations with the most device time, summed over the
    chips (a loop's time includes its body's), and the ten longest idle
    gaps, each named by the host span open in it."""
    total = {}
    lo, hi = tr.window
    for spans in tr.ops.values():
        for name, s, e in trace_lib.overlapping(spans, lo, hi):
            name = trace_lib.short_name(name)
            total[name] = total.get(name, 0.0) + (e - s) * 1e-9
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted((g for dev in tr.ops for g in trace_lib.idle_gaps(tr, dev)),
                  key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": [[trace_lib.host_span_at(tr, (s + e) / 2),
                           (e - s) * 1e-9] for s, e in gaps]}


def reference_gaps(ref, ref_cfg: dict, seed: int, dev_of: dict, picks,
                   length: int, control: bool):
    """check.logit_gaps over ``picks``, each on the chip of the replica
    that served it, one chip's float32 weights (and the control's) held
    at a time."""
    held = {}

    def weights_on(i, kind="f32"):
        d = dev_of[i]
        if d not in held:
            held.clear()
            gc.collect()
            held[d] = {"f32": ref.weights(seed, ref_cfg, device=d)}
        if kind not in held[d]:
            held[d][kind] = ref.fp8_weights(held[d]["f32"])
        return held[d][kind]
    return check.logit_gaps(
        ref, weights_on, ref_cfg, sorted(picks, key=lambda s: s.replica),
        length, control_on=(lambda i: weights_on(i, "fp8")) if control
        else None)


def run(args, rehearse: bool = False, break_with=None,
        control: bool = False) -> dict:
    """One run; returns the result line's object.  ``rehearse`` is the
    CPU rehearsal of the tests: no look for a chip, the program's small
    preset, JAX's persistent cache left alone.  ``break_with(engines,
    cluster)`` breaks the timed path before it is served (the tests'
    planted faults).  ``control`` also reads the control's gap
    (control.py)."""
    cell = spec.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                          args.workload)
    cfg, mix = cell.config, cell.traffic
    load_program()
    import jax
    import numpy as np
    devices = jax.devices()
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        raise RunError(f"needs a TPU, JAX found {dev.platform}")
    if len(devices) < cell.chips:
        raise RunError(f"{args.workload} needs {cell.chips} chips, JAX "
                       f"found {len(devices)}")
    peaks = None if rehearse else spec.peaks(dev.device_kind)
    from repro.core.profiles import HardwareProfile
    from repro.launch.serve import (build_engine_fleet, use_compile_cache,
                                    warm_fleet)
    from repro.serving.gateway import (EngineClusterAdapter, Gateway,
                                       GatewayConfig)
    from repro.serving.policies import make_gateway_policy
    from repro.serving.request import Request
    if not rehearse:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.hit)
    try:
        replicas = int(cfg["replicas"])
        engines = build_engine_fleet(
            cfg["arch"], replicas, reduced=rehearse,
            n_slots=int(cfg["n_slots"]), cache_len=int(cfg["cache_len"]),
            seed=args.seed, profile=HardwareProfile(**cfg["profile"]))
        pcfg = engines[0].cfg
        ref = spec.reference(cfg["arch"])
        off = [] if rehearse else ref.departures(cfg, pcfg)
        if off:
            raise RunError("the program departs from the configuration: "
                           + "; ".join(off))
        draws = traffic_lib.stream(mix, seed=args.seed, replicas=replicas,
                                   cache_len=int(cfg["cache_len"]),
                                   vocab=pcfg.vocab_size)
        warm_fleet(engines, sorted({d.prompt for d in draws}))
        cluster = EngineClusterAdapter(engines)
        if break_with is not None:
            break_with(engines, cluster)
        reqs = [Request(prompt_tokens=d.prompt, decode_tokens=d.decode,
                        arrival=d.arrival, task=d.task, tenant="bench",
                        tokens=d.tokens.tolist()) for d in draws]
        tracer = TraceWindow(min(TRACE_SECONDS, args.seconds)) \
            if args.trace else None
        setup_built = None

        def after_advance(r, t):
            nonlocal setup_built
            if setup_built is None and r.t_open is not None:
                setup_built = compiles.built
            if tracer is not None:
                tracer(r, t)
        rec = replay.Recorder(cluster,
                              warmup_s=float(mix["warmup_s"]),
                              seconds=float(args.seconds),
                              annotate=bool(args.trace),
                              on_advance=after_advance)
        policy = replay.TimedPolicy(make_gateway_policy(cfg["policy"]),
                                    annotate=bool(args.trace))
        gw = Gateway(GatewayConfig(), None, policy, cluster=rec)
        try:
            gw.run(reqs)
            raise RunError("the stream ran out before the window closed: "
                           "lengthen the traffic's horizon_s")
        except replay.WindowClosed:
            pass
        if tracer is not None:
            tracer.stop(rec)
        setup_s = rec.t_open - _T0
        window_compiles = compiles.built - (setup_built or 0)
        jax.block_until_ready([e.cache for e in engines])
        used = sorted({e.device for e in engines}, key=lambda d: d.id) \
            if engines[0].device is not None else [dev]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
        e2e = endtoend.compute(rec)
        done = rec.completed_in_window()
        served_by = {rid: r.instance for rid, r in rec.requests.items()}
        served = [check.Served(rid=rid, replica=rec.requests[rid].instance,
                               prompt=list(rec.requests[rid].tokens),
                               output=list(rec.requests[rid].output_tokens),
                               decode_tokens=rec.requests[rid].decode_tokens)
                  for rid in done]
        faults = check.served_faults(
            served, {rid: len(ks) for rid, ks in rec.done_adv.items()},
            replicas, pcfg.vocab_size)
        per_layer = None
        if tracer is not None:
            per_layer = trace_context(tracer, rec, policy, served_by, cfg,
                                      peaks, e2e, used)
            shutil.rmtree(tracer.dir, ignore_errors=True)
        # free the program's state before the reference runs
        ref_cfg = ref.config_of(pcfg) if rehearse else cfg
        dev_of = {i: e.device for i, e in enumerate(engines)}
        for e in engines:
            for leaf in jax.tree.leaves((e.params, e.cache)):
                leaf.delete()
        del gw, rec, engines, reqs, cluster
        gc.collect()
        picks = check.sample(served, args.seed, replicas)
        gaps, control_gaps = reference_gaps(
            ref, ref_cfg, args.seed, dev_of, picks,
            int(cfg["cache_len"]), control)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        jax.monitoring.unregister_event_listener(compiles.hit)
    worst_gap = max(gaps) if gaps else float("inf")
    checks = {
        "served_faults": {"value": faults, "limit": 0},
        "logit_gap": {"value": worst_gap, "limit": check.LOGIT_GAP_LIMIT},
    }
    correct = (faults == 0 and worst_gap <= check.LOGIT_GAP_LIMIT)
    info = dict(e2e)
    info.update(setup_s=setup_s, setup_compiles=setup_built,
                window_compiles=window_compiles,
                checked_requests=len(picks),
                checked_tokens=sum(len(s.output) for s in picks),
                mean_slots_in_use=e2e["resident_mean"] / replicas)
    metrics, bd = {}, None
    if per_layer is None:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m.name] = {"value": values[m.name], "unit": m.unit}
    else:
        for m in cell.per_layer:
            v = m.read(per_layer)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        bd = breakdown(per_layer.trace)
    result = {"correct": bool(correct), "attempted": len(served),
              "failed": faults, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": int(peak)}}
    if per_layer is not None:
        tr = per_layer.trace
        lo, hi = tr.window
        busy = [trace_lib.busy_ns(tr, d) for d in per_layer.devices]
        result["device"]["busy_s"] = float(np.mean(busy)) * 1e-9
        result["device"]["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = bd
    if control:
        result["control"] = {"logit_gap": max(control_gaps),
                             "per_request": control_gaps,
                             "program_per_request": gaps}
    result["info"] = info
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except (RunError, spec.SpecError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    for k, v in result["info"].items():
        print(f"info {k} {v}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
