"""End-to-end serving example: the gateway fronting a four-instance
simulated cluster with a trained intelligent router restored from its
checkpoint, a scheduled instance crash and restart with failover, and
an elastic scale-out when the windowed P95 E2E climbs -- the router
adapts (the decomposed Q network scores any instance count).

  PYTHONPATH=src python examples/serve_cluster.py
"""
import tempfile

from repro.core import rl_router as rl
from repro.core.profiles import V100_LLAMA2_7B
from repro.core.workload import generate, to_requests
from repro.serving.chaos import Crash, FaultSchedule
from repro.serving.gateway import Gateway, GatewayConfig, OracleLength
from repro.serving.policies import restore_rl_policy
from repro.training.checkpoint import CheckpointManager

PROF = V100_LLAMA2_7B

if __name__ == "__main__":
    router_cfg = rl.RouterConfig(variant="guided", n_instances=4,
                                 explore_episodes=3, q_arch="decomposed",
                                 seed=0)
    # short warm-up training
    out = rl.train(router_cfg, PROF,
                   lambda ep: to_requests(generate(200, seed=ep),
                                          rate=20.0, seed=ep + 50),
                   n_episodes=4)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        # checkpoint the trained router, then serve from the restored
        # copy (the path ``launch.serve --policy rl --checkpoint`` takes)
        CheckpointManager(ckpt_dir).save(0, out["agent"].state_dict(),
                                         sync=True)
        policy = restore_rl_policy(router_cfg, ckpt_dir, m=4)
    print("router checkpoint restored OK")
    # instance 2 crashes at t=5 s and restarts 15 s later; its orphans
    # re-enter admission through the bounded-retry failover path
    chaos = FaultSchedule(crashes=(Crash(5.0, 2, restart_after=15.0),))
    gw = Gateway(GatewayConfig(chaos=chaos, failover=True),
                 (PROF,) * 4, policy, length=OracleLength(),
                 scale_up_when=lambda shed_rate, p95_e2e: p95_e2e > 20.0)
    reqs = to_requests(generate(400, seed=991), rate=20.0, seed=992)
    stats = gw.run(reqs)
    print("== gateway with fault injection and autoscaling ==")
    print(f"served n={stats['n']} e2e={stats['e2e_mean']:.2f}s "
          f"ttft={stats['ttft_mean']:.2f}s "
          f"preemptions={stats['preemptions']}")
    print(f"chaos: orphaned={stats['orphaned']} "
          f"retried={stats['retried']}; scale-outs at "
          f"{[round(t, 2) for t in gw.scale_events]} "
          f"(now {gw.cluster.m} instances)")
    assert stats["n"] == 400, "every request must complete despite failure"
