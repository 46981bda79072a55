"""End-to-end driver (deliverable (b)): supervised warm-start + fully
asynchronous GIPO fine-tuning on the built-in manipulation suite.

    PYTHONPATH=src python examples/train_async.py \
        --arch deepseek-7b --suite spatial --steps 200

``--preset tiny`` (default) runs in minutes on CPU; ``--preset 100m``
builds a ~100M-parameter backbone (same code path — expect hours on CPU,
it is meant for real accelerators).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

sys.path.insert(0, "benchmarks")

import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.configs.base import RLConfig, RuntimeConfig
from repro.envs.toy_manipulation import SUITES, lognormal_latency
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import AcceRLSystem


def build_cfg(arch: str, preset: str):
    cfg = get_config(arch)
    if preset == "tiny":
        cfg = reduced(cfg, layers=2, d_model=128)
    elif preset == "100m":
        cfg = reduced(cfg, layers=8, d_model=1024, vocab=8192)
        cfg = dataclasses.replace(cfg, head_dim_override=None, num_heads=16,
                                  num_kv_heads=4 if cfg.num_kv_heads else 0,
                                  d_ff=4096 if cfg.d_ff else 0)
    if cfg.num_prefix_tokens == 0:
        cfg = dataclasses.replace(cfg, num_prefix_tokens=1)
    return cfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b", choices=ASSIGNED_ARCHS)
    ap.add_argument("--suite", default="spatial", choices=SUITES)
    ap.add_argument("--preset", default="tiny", choices=("tiny", "100m"))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--workers", type=int, default=6)
    ap.add_argument("--wall-minutes", type=float, default=15.0)
    ap.add_argument("--bc-episodes", type=int, default=40)
    ap.add_argument("--algo", default="gipo", choices=("gipo", "ppo"))
    ap.add_argument("--sync", action="store_true",
                    help="run the synchronous BASELINE instead (Fig. 1 left)")
    ap.add_argument("--backpressure", default="drop_oldest",
                    choices=("drop_oldest", "drop_newest", "block"),
                    help="experience-channel policy when B is full")
    args = ap.parse_args()
    enable_compile_cache()

    from common import bc_train, collect_demos, eval_policy  # benchmarks/

    cfg = build_cfg(args.arch, args.preset)
    print(f"[1/3] BC warm-start on {args.bc_episodes} oracle episodes "
          f"({cfg.param_count()/1e6:.1f}M params)")
    demos = collect_demos(args.suite, cfg, episodes=args.bc_episodes)
    bc_params, losses = bc_train(cfg, demos, steps=150)
    sft = eval_policy(cfg, bc_params, args.suite, episodes=10)
    print(f"      BC loss {losses[0]:.3f}->{losses[-1]:.3f}; "
          f"SFT success {sft['success_rate']:.2f}")

    rl = RLConfig(algo=args.algo, grad_accum=1, lr_policy=5e-5,
                  lr_value=5e-4, gipo_sigma=0.5, kl_coef=0.05)
    rt = RuntimeConfig(num_rollout_workers=args.workers, inference_batch=8,
                       replay_backpressure=args.backpressure)
    sys_ = AcceRLSystem(cfg, rl, rt, suite=args.suite, segment_horizon=6,
                        max_episode_steps=14, batch_episodes=8,
                        latency=lognormal_latency(2.0, sigma=1.0))
    sys_.trainer.state = sys_.trainer.state._replace(params=bc_params)

    mode = "SYNC baseline" if args.sync else "ASYNC AcceRL"
    print(f"[2/3] {mode}: {args.steps} trainer steps, "
          f"{args.workers} rollout workers")
    # same services either way — only the scheduler differs; a crashed
    # service raises ServiceFailure here (non-zero exit with its traceback)
    runner = sys_.run_sync if args.sync else sys_.run_async
    m = runner(train_steps=args.steps,
               wall_timeout_s=args.wall_minutes * 60)
    print(f"      wall {m['wall_s']:.1f}s | env SPS {m['sps_env']:.1f} | "
          f"trainer util {m['trainer_util']:.2f} | "
          f"policy lag {m['mean_policy_lag']:.2f} | "
          f"rollout success {m['success_rate']:.2f}")
    unhealthy = {k: h for k, h in sys_.health().items() if not h["healthy"]}
    if unhealthy:
        sys.exit(f"unhealthy services: {unhealthy}")

    print("[3/3] final evaluation")
    final = sys_.evaluate(episodes=20)
    print(f"      success {final['success_rate']:.2f} "
          f"(SFT was {sft['success_rate']:.2f}) | "
          f"return {final['mean_return']:.2f}")


if __name__ == "__main__":
    main()
