"""Smoke run of the served path on a TPU: qwen3-0.6b engine replicas at
published widths (bf16, Pallas kernels, 8 slots x 2048 cache) behind the
gateway, with weights and prompts drawn from ``--seed``.

  python chip_smoke.py              # one chip, one replica
  python chip_smoke.py --chips 4    # four replicas, one per chip, and
                                    # the same stream on one replica

One chip, in phases:
  (a) the first device must be a TPU;
  (b) build the replica, compile every prefill length the stream uses
      and the decode step, and check that the lowered programs hold
      the Pallas kernels (``tpu_custom_call``);
  (c) serve a seeded stream through the Gateway under the mixing
      policy; every request must finish with its decode_tokens ids;
  (d) replay four served requests through the engine's own steps and
      compare its logits with a float32 reference forward (jnp
      attention, ``highest`` matmul precision) on the chip.
With ``--chips 4`` it runs (c) on one replica on chip 0, then on four
replicas, one per chip, and checks placement, that every chip served,
and (d) for one request per chip.

Any failure exits non-zero.  The last line, printed only on success, is
one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen3-0.6b"
N_SLOTS, CACHE_LEN = 8, 2048
N_REQUESTS = 16
PROMPTS = (16, 1500)          # token counts, drawn log-uniform
DECODES = (8, 64)             # token counts, drawn uniform (inclusive)
ARRIVAL_GAP_S = 0.01          # virtual seconds between arrivals
N_CHECKED = 4
# max |engine logit - reference logit| over max |reference logit|, for
# bf16 weights and KV against float32 (the bf16 model test uses 5%)
TOL = 0.05


class SmokeError(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeError(msg)


def draw_stream(seed: int, vocab: int):
    """N_REQUESTS requests with seeded lengths and prompt ids."""
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(N_REQUESTS):
        p = int(np.exp(rng.uniform(*np.log(PROMPTS))).round())
        d = int(rng.integers(DECODES[0], DECODES[1] + 1))
        reqs.append(Request(prompt_tokens=p, decode_tokens=d,
                            arrival=ARRIVAL_GAP_S * i, tenant="smoke",
                            tokens=rng.integers(0, vocab, p).tolist()))
    return reqs


def warm(engines, reqs) -> float:
    from repro.launch.serve import warm_fleet
    t0 = time.perf_counter()
    warm_fleet(engines, [r.prompt_tokens for r in reqs])
    return time.perf_counter() - t0


def kernels_in_programs(engine, prompt_len: int) -> None:
    """The lowered prefill and decode programs call the Pallas kernels
    (not interpret mode, not the jnp path)."""
    import jax
    toks = jax.device_put(np.zeros((1, prompt_len), np.int32),
                          engine.device)
    prefill = engine.prefill_fn.lower(engine.params, toks).as_text()
    decode = engine.decode_fn.lower(engine.params, engine.cache,
                                    engine.tokens).as_text()
    for name, text in (("prefill", prefill), ("decode", decode)):
        n = text.count("tpu_custom_call")
        check(n > 0, f"{name}: no tpu_custom_call in the lowered program")
        print(f"  {name}: tpu_custom_call found ({n} sites)")


def serve(engines, reqs):
    """Serve ``reqs`` through the gateway; returns the wall seconds up
    to the last device result."""
    import jax
    from repro.serving.gateway import (EngineClusterAdapter, Gateway,
                                       GatewayConfig)
    from repro.serving.policies import make_gateway_policy
    gw = Gateway(GatewayConfig(), None, make_gateway_policy("mixing"),
                 cluster=EngineClusterAdapter(engines))
    t0 = time.perf_counter()
    stats = gw.run(reqs)
    jax.block_until_ready([e.cache for e in engines])
    wall = time.perf_counter() - t0
    for r in reqs:
        check(r.finished is not None, f"request {r.rid} did not finish")
        check(len(r.output_tokens) == r.decode_tokens,
              f"request {r.rid}: {len(r.output_tokens)} ids for "
              f"{r.decode_tokens} decode tokens")
    vocab = engines[0].cfg.vocab_size
    ids = np.concatenate([r.output_tokens for r in reqs])
    check(((ids >= 0) & (ids < vocab)).all(), "generated id out of vocab")
    print(f"  served {stats['n']}/{len(reqs)} requests, "
          f"{sum(r.prompt_tokens for r in reqs)} prompt tokens, "
          f"{ids.size} generated tokens, preemptions "
          f"{stats['preemptions']}")
    print(f"  chip wall seconds (gateway run to block_until_ready): "
          f"{wall:.3f}")
    print(f"  virtual-clock makespan (profile, not measured): "
          f"{stats['makespan']:.3f} s")
    return wall


def replay_logits(engine, req) -> np.ndarray:
    """The engine's own logits at each generated position: its prefill
    on the prompt, then decode steps fed the served ids, in slot 0 of the
    idle replica.  Row k predicts ``req.output_tokens[k]``."""
    import jax
    check(not engine.resident and not engine.queue, "replica not idle")
    toks = jax.device_put(np.asarray(req.tokens, np.int32)[None],
                          engine.device)
    logits, small = engine.prefill_fn(engine.params, toks)
    engine.cache = engine.insert_fn(engine.cache, small, 0)
    rows = [np.asarray(logits[0], np.float32)]
    feed = np.zeros((engine.n_slots,), np.int32)
    for t in req.output_tokens[:-1]:
        feed[0] = t
        logits, engine.cache = engine.decode_fn(
            engine.params, engine.cache,
            jax.device_put(feed, engine.device))
        rows.append(np.asarray(logits[0], np.float32))
    return np.stack(rows)


def reference_logits(forward, req, length: int) -> np.ndarray:
    """The float32 forward over prompt + served ids, at the positions
    that predicted each served id.  The sequence is zero-padded to
    ``length`` so every request shares one compile; causal attention
    keeps the padding out of every earlier position."""
    import jax
    seq = list(req.tokens) + req.output_tokens[:-1]
    toks = np.zeros((1, length), np.int32)
    toks[0, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(toks)
    return np.asarray(logits[0, req.prompt_tokens - 1:len(seq)], np.float32)


def check_answers(engines, reqs) -> None:
    """(d): for each request, the engine's logits against the float32
    reference (max-abs error over max |reference logit| <= TOL), and each
    served id within 2 * TOL of the reference's top logit."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib
    e0 = engines[0]
    ref_cfg = dataclasses.replace(e0.cfg, dtype="float32", use_pallas=False)
    ref = jax.tree.map(lambda x: x.astype(jnp.float32), e0.params)
    fwd = jax.jit(model_lib.forward_train, static_argnums=1)
    forward = functools.partial(fwd, ref, ref_cfg)
    length = max(r.prompt_tokens + r.decode_tokens - 1 for r in reqs)
    worst = 0.0
    for r in reqs:
        got = replay_logits(engines[r.instance], r)
        want = reference_logits(forward, r, length)
        scale = float(np.abs(want).max())
        err = float(np.abs(got - want).max()) / scale
        ids = np.asarray(r.output_tokens)
        gap = float((want.max(-1) - want[np.arange(ids.size), ids]).max())
        agree = float((got.argmax(-1) == ids).mean())
        print(f"  request {r.rid}: replica {r.instance} prompt "
              f"{r.prompt_tokens} decode {r.decode_tokens}: logit error "
              f"{err:.5f} (tolerance {TOL}), served-id gap to reference "
              f"top {gap / scale:.5f} (tolerance {2 * TOL}), replay "
              f"argmax = served ids {agree:.3f}")
        check(err <= TOL, f"request {r.rid}: logit error {err:.5f} > {TOL}")
        check(gap <= 2 * TOL * scale,
              f"request {r.rid}: served ids stray from the reference")
        worst = max(worst, err)
    print(f"  reference check passed: worst logit error {worst:.5f} over "
          f"{len(reqs)} requests (tolerance {TOL})")


def spread(reqs, n: int):
    """``n`` requests spread over the prompt lengths served."""
    by_len = sorted(reqs, key=lambda r: r.prompt_tokens)
    picks = np.linspace(0, len(by_len) - 1, n).round().astype(int)
    return [by_len[i] for i in picks]


def one_chip(seed: int) -> None:
    from repro.launch.serve import build_engine_fleet
    print("(b) build and compile")
    engines = build_engine_fleet(ARCH, 1, n_slots=N_SLOTS,
                                 cache_len=CACHE_LEN, seed=seed)
    reqs = draw_stream(seed, engines[0].cfg.vocab_size)
    lengths = sorted({r.prompt_tokens for r in reqs})
    cfg = engines[0].cfg
    print(f"  {cfg.name} d_model {cfg.d_model} {cfg.dtype} use_pallas "
          f"{cfg.use_pallas}, {N_SLOTS} slots x {CACHE_LEN} cache; "
          f"{len(lengths)} prefill lengths {lengths}")
    print(f"  compile + warm-up seconds: {warm(engines, reqs):.1f}")
    kernels_in_programs(engines[0], max(lengths))
    print("(c) serve through the gateway (mixing policy)")
    serve(engines, reqs)
    print("(d) reference check")
    check_answers(engines, spread(reqs, N_CHECKED))


def on_device(tree, device) -> bool:
    import jax
    return all(x.devices() == {device} for x in jax.tree.leaves(tree))


def four_chips(seed: int) -> None:
    import jax
    from repro.launch.serve import build_engine_fleet
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, have "
          f"{len(devices)}")
    fleet = build_engine_fleet(ARCH, 4, n_slots=N_SLOTS,
                               cache_len=CACHE_LEN, seed=seed)
    solo = build_engine_fleet(ARCH, 1, n_slots=N_SLOTS,
                              cache_len=CACHE_LEN, seed=seed)
    for i, e in enumerate(fleet + solo):
        check(on_device(e.params, devices[i % 4])
              and on_device(e.cache, devices[i % 4]),
              f"replica {i} is not wholly on {devices[i % 4]}")
    print(f"  replica i's params and cache sit on jax.devices()[i]: "
          f"{[str(d) for d in devices]}")
    vocab = solo[0].cfg.vocab_size
    reqs = draw_stream(seed, vocab)
    print(f"  compile + warm-up seconds (5 replicas on 4 chips): "
          f"{warm(fleet + solo, reqs):.1f}")
    print("one replica on chip 0")
    solo_reqs = draw_stream(seed, vocab)
    wall1 = serve(solo, solo_reqs)
    del solo
    print("four replicas, one per chip")
    wall4 = serve(fleet, reqs)
    served = [sum(r.instance == i for r in reqs) for i in range(4)]
    print(f"  requests served per chip: {served}")
    check(all(served), "a chip served no request")
    same = np.mean([a.output_tokens == b.output_tokens
                    for a, b in zip(reqs, solo_reqs)])
    print(f"  answers identical to the one-replica run: {same:.3f}")
    print(f"  chip wall seconds: one replica {wall1:.3f}, four replicas "
          f"{wall4:.3f}")
    print("(d) reference check, one request per chip")
    check_answers(fleet, [max((r for r in reqs if r.instance == i),
                              key=lambda r: r.prompt_tokens)
                          for i in range(4)])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"FAIL: {ROOT} is not a checkout of this repo (no src/repro)",
              file=sys.stderr)
        return 2
    import jax
    dev = jax.devices()[0]
    print(f"(a) device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(jax.devices())}")
    if dev.platform != "tpu":
        print(f"FAIL: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    from repro.launch.serve import use_compile_cache
    print(f"  compile cache: {use_compile_cache()}")
    try:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
