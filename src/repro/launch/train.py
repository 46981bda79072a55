"""Production training launcher.

On a real TPU pod this runs the sharded ``seq_train_step`` over the
production mesh; on CPU (``--local``) it runs the same program on a 1×1
mesh with a reduced config — the code path is identical, only the mesh and
scale differ.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --shape train_4k --steps 3 --local

``--remote-rollout N`` switches to the asynchronous runtime demo instead:
an :class:`AcceRLSystem` with N rollout worker processes hosted by the
Supervisor behind the transport subsystem (socket channels + weight-store
wire), trained for ``--steps`` policy updates on a reduced config:

    PYTHONPATH=src python -m repro.launch.train --remote-rollout 2 --steps 3

``--serve-workers N`` is the two-terminal multi-host demo: this process
binds ``--listen`` and waits for N connect-mode workers to dial in with
``--token``; each worker is a separate ``repro.launch.worker`` process
(any reachable host):

    # terminal 1
    PYTHONPATH=src python -m repro.launch.train --serve-workers 1 \
        --listen 127.0.0.1:5555 --token sekrit --steps 3
    # terminal 2
    PYTHONPATH=src python -m repro.launch.worker \
        --address 127.0.0.1:5555 --token sekrit

``--restart on_failure`` puts either flavor under a restart budget: a
killed worker is respawned (spawn mode) or its slot re-opened for a
redial (connect mode) instead of failing the run.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

# --trace-out arms the observability plane. Tracing is IMPORT-gated (every
# instrumented module binds its _tel at import time, keeping the off path
# free), so the env flag must be up before ANY repro import below —
# argparse has not run yet, scan argv directly.
if any(a == "--trace-out" or a.startswith("--trace-out=")
       for a in sys.argv[1:]):
    os.environ.setdefault("REPRO_TRACE", "1")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config, get_shape, reduced
from repro.configs.base import RLConfig
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b",
                    choices=ASSIGNED_ARCHS)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--local", action="store_true",
                    help="reduced config on the local device mesh (CPU demo)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fused-loss", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the action head + GIPO loss tail block-fused "
                         "(kernels/dispatch.py) — no [B,S,Va] logits in "
                         "HBM; default ON, --no-fused-loss opts out")
    ap.add_argument("--kernel-dispatch", default="auto",
                    choices=("auto", "pallas", "jnp"),
                    help="hot-op routing: Pallas on TPU / jnp twins "
                         "elsewhere (auto), or force one side")
    ap.add_argument("--remote-rollout", type=int, default=0, metavar="N",
                    help="run the async AcceRLSystem demo with N rollout "
                         "worker processes spawned under the Supervisor "
                         "(reduced config; ignores --shape)")
    ap.add_argument("--serve-workers", type=int, default=0, metavar="N",
                    help="host N connect-mode worker slots and wait for "
                         "repro.launch.worker processes to dial in "
                         "(two-terminal multi-host demo)")
    ap.add_argument("--listen", default="127.0.0.1:0", metavar="HOST:PORT",
                    help="TransportServer bind address for --serve-workers")
    ap.add_argument("--token", default="",
                    help="shared worker.hello secret for --serve-workers")
    ap.add_argument("--restart", default="never",
                    choices=("never", "on_failure"),
                    help="supervision policy for remote/connect workers")
    ap.add_argument("--max-restarts", type=int, default=2,
                    help="restart budget per worker slot (with "
                         "--restart on_failure)")
    ap.add_argument("--remote-transport", default="socket",
                    choices=("socket", "shm", "ring"),
                    help="experience/weight wire for --remote-rollout: "
                         "per-message sockets, per-message SHM segments, "
                         "or persistent SHM rings (streaming data plane)")
    ap.add_argument("--put-window", type=int, default=0, metavar="W",
                    help="pipeline rollout flushes through a PutStream "
                         "with W frames in flight (0 = one RPC per flush; "
                         "ring transport always streams)")
    ap.add_argument("--journal-dir", default="", metavar="DIR",
                    help="write-ahead journal the TransportServer's hosted "
                         "state (channel contents, stream watermarks, "
                         "weight publishes) into DIR so a replacement "
                         "server can recover it")
    ap.add_argument("--resume-journal", action="store_true",
                    help="recover --journal-dir's state at startup (the "
                         "replacement-server path after a crash) instead "
                         "of requiring the directory to be fresh")
    ap.add_argument("--elastic-workers", type=int, default=0, metavar="MAX",
                    help="autoscale the remote worker fleet up to MAX "
                         "slots from queue-depth/weight-staleness signals "
                         "(0 = fixed fleet)")
    ap.add_argument("--inference-plane", default="", metavar="MODE",
                    choices=("", "host", "spawn"),
                    help="disaggregated inference for remote workers: "
                         "'host' serves the parent's pool behind the "
                         "transport, 'spawn' runs a supervised shared "
                         "inference tier process; default: each worker "
                         "keeps a colocated pool")
    ap.add_argument("--pipeline", action="store_true",
                    help="run the pipelined training-runtime demo: policy "
                         "trainer + world-model trainer as pipeline stages "
                         "on submeshes of the local device set, driven by "
                         "the static RUN/SEND/RECV/FREE schedules "
                         "(runtime/pipeline_exec.py); reduced config, "
                         "ignores --shape")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="write a Chrome-trace-event JSON (open in "
                         "Perfetto / chrome://tracing) covering every "
                         "process of the run; also arms the REPRO_TRACE "
                         "span recorder and the telemetry sink")
    args = ap.parse_args()
    if args.resume_journal and not args.journal_dir:
        ap.error("--resume-journal needs --journal-dir")
    enable_compile_cache()

    if args.pipeline:
        _run_pipeline(args)
        return
    if args.remote_rollout or args.serve_workers:
        _run_remote_rollout(args)
        return

    cfg = get_config(args.arch)
    shape = get_shape(args.shape)
    assert shape.kind == "train", "use repro.launch.serve for decode shapes"

    if args.local:
        cfg = reduced(cfg, layers=2, d_model=128)
        shape = dataclasses.replace(shape, seq_len=256, global_batch=4)
        mesh = make_local_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    rl = RLConfig(fused_loss=args.fused_loss,
                  kernel_dispatch=args.kernel_dispatch)
    if args.kernel_dispatch != "auto":
        # process-wide routing so attention / ssd_scan inside the
        # transformer follow the same side as the loss tail
        from repro.kernels import dispatch
        dispatch.set_mode(args.kernel_dispatch)
    accum = steps.choose_accum(cfg, shape, mesh)
    structs, batch_structs, sspec, bspec = steps.train_specs(
        cfg, shape, mesh, accum=accum)
    print(f"mesh {dict(mesh.shape)} | accum {accum} | "
          f"params {cfg.param_count()/1e6:.1f}M")

    with mesh:
        import functools
        fn = functools.partial(steps.seq_train_step, cfg=cfg, rl=rl,
                               accum=accum, grad_shardings=sspec.params)
        jfn = jax.jit(fn, in_shardings=(sspec, bspec),
                      out_shardings=(sspec, None))

        # materialize state + synthetic batch with the right shardings
        key = jax.random.PRNGKey(0)
        from repro.models.policy import init_policy_params
        from repro.optim import adamw
        from repro.core.advnorm import init_adv_state
        params = init_policy_params(cfg, key)
        state = steps.SeqTrainState(params=params, opt=adamw.init(params),
                                    adv_norm=init_adv_state())
        state = jax.device_put(state, sspec)
        rng = np.random.default_rng(0)
        batch = {
            k: jax.device_put(jnp.asarray(
                rng.integers(0, cfg.vocab_size, v.shape).astype(v.dtype)
                if v.dtype == jnp.int32 else
                rng.standard_normal(v.shape).astype(np.float32) * 0.1),
                bspec[k])
            for k, v in batch_structs.items()
        }
        for i in range(args.steps):
            t0 = time.perf_counter()
            state, metrics = jfn(state, batch)
            jax.block_until_ready(metrics["loss"])
            print(f"step {i}: loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.2f} "
                  f"({time.perf_counter() - t0:.2f}s)")


def _run_pipeline(args) -> None:
    """Pipelined training-runtime demo (reduced config): the world-model
    system with ``rt.pipeline`` on — the policy trainer's optimizer step
    and the WM trainer run as pipeline stages on submeshes of the local
    device list, one static instruction schedule per submesh."""
    from repro.configs.base import RuntimeConfig, TelemetryConfig, WMConfig
    from repro.wm.wm_system import AcceRLWMSystem

    cfg = reduced(get_config(args.arch), layers=2, d_model=64)
    rl = RLConfig(grad_accum=2, lr_policy=1e-4, lr_value=1e-3,
                  fused_loss=args.fused_loss,
                  kernel_dispatch=args.kernel_dispatch)
    rt = RuntimeConfig(num_rollout_workers=2, inference_batch=4,
                       pipeline=True,
                       telemetry=TelemetryConfig(sink=bool(args.trace_out),
                                                 trace_out=args.trace_out))
    wm = WMConfig(imagine_horizon=2, history_frames=2, diffusion_steps=4,
                  obs_train_interval=2, reward_train_interval=5)
    system = AcceRLWMSystem(cfg, rl, rt, wm, suite="spatial",
                            segment_horizon=4, max_episode_steps=8,
                            imagination_batch=4)
    layout = system.trainer._layout
    print(f"pipeline: policy submesh {[str(d) for d in layout.policy.devices]}"
          f" | wm submesh {[str(d) for d in layout.wm.devices]}"
          f" | disjoint={layout.disjoint} | K={rl.grad_accum}")
    t0 = time.time()
    m = system.run_wm(train_steps=args.steps, wall_timeout_s=300.0)
    pipe = system.trainer.pipeline
    print(f"trained {m['train_steps']} policy steps "
          f"({pipe.rounds} pipeline rounds) in {time.time() - t0:.1f}s | "
          f"imagined {m['imagined_steps']} steps | "
          f"wm updates {m['wm_updates']}")
    print(f"bubble frac {pipe.last_bubble} | "
          f"peak live grad bytes {pipe.peak_grad_bytes}")
    if args.trace_out:
        from repro.runtime import telemetry
        n = telemetry.dump(args.trace_out, process_name="train-pipeline")
        print(f"trace: {n} events -> {args.trace_out}")


def _run_remote_rollout(args) -> None:
    """Asynchronous-system demo with supervised remote rollout workers —
    spawned child processes and/or connect-mode workers dialing in."""
    from repro.configs import reduced
    from repro.configs.base import (RuntimeConfig, SupervisionConfig,
                                    TelemetryConfig, TransportConfig)
    from repro.runtime import AcceRLSystem

    cfg = reduced(get_config(args.arch), layers=2, d_model=64)
    rl = RLConfig(grad_accum=1, lr_policy=1e-4, lr_value=1e-3,
                  fused_loss=args.fused_loss,
                  kernel_dispatch=args.kernel_dispatch)
    # spawned workers roll out alone: a local worker would reach the step
    # budget before a child has started, and the demo would show no wire
    local = 0 if args.remote_rollout else 1
    rt = RuntimeConfig(
        num_rollout_workers=local, inference_batch=4,
        transport=TransportConfig(
            remote_rollout_workers=args.remote_rollout,
            connect_rollout_workers=args.serve_workers,
            kind=args.remote_transport,
            put_window=args.put_window,
            listen_addr=args.listen if args.serve_workers else "",
            token=args.token,
            journal_dir=args.journal_dir,
            resume_journal=args.resume_journal,
            inference_plane=args.inference_plane,
            reconnect_attempts=(20 if args.inference_plane else 0),
            supervision=SupervisionConfig(
                restart=args.restart,
                max_restarts=args.max_restarts,
                max_workers=args.elastic_workers,
                min_workers=(1 if args.elastic_workers else 0))),
        telemetry=TelemetryConfig(sink=bool(args.trace_out),
                                  trace_out=args.trace_out))
    system = AcceRLSystem(cfg, rl, rt, suite="spatial", segment_horizon=4,
                          max_episode_steps=12, batch_episodes=4)
    host, port = system.transport_server.address
    print(f"async system: {local} local + {args.remote_rollout} spawned + "
          f"{args.serve_workers} connect-mode rollout worker(s) over "
          f"{args.remote_transport} @ {host}:{port} "
          f"(restart={args.restart}"
          + (f", inference={args.inference_plane}" if args.inference_plane
             else "") + ")")
    if args.serve_workers:
        token_arg = f" --token {args.token}" if args.token else ""
        print(f"dial in from another terminal/host:\n"
              f"  PYTHONPATH=src python -m repro.launch.worker "
              f"--address {host}:{port}{token_arg}")
    t0 = time.time()
    m = system.run_async(train_steps=args.steps, wall_timeout_s=300.0)
    print(f"trained {m['train_steps']} steps in {time.time() - t0:.1f}s | "
          f"env SPS {m['sps_env']:.1f} | policy lag "
          f"{m['mean_policy_lag']:.2f}")
    for name, h in system.health().items():
        line = f"  {name:20s} {h['state']}"
        snap = m["services"].get(name, {})
        counters = snap.get("counters", {})
        for key in ("env_steps", "steps", "batches", "requests"):
            if key in counters:
                line += f"  {key}={int(counters[key])}"
        print(line + (f"  error={h['error']}" if h["error"] else ""))
    if args.trace_out:
        # one file covers every process: the parent's own buffers plus
        # the child events the server folded in from worker.report
        from repro.runtime import telemetry
        n = telemetry.dump(args.trace_out, process_name="train-parent")
        print(f"trace: {n} events -> {args.trace_out}")


if __name__ == "__main__":
    main()
