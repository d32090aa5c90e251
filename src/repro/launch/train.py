"""Training launcher.

Runs on however many devices exist: everything on one device, or with
``--mesh`` the agents spread over all of them (optionally forced host
devices via --force-devices, which must be set before jax initializes —
hence the env re-exec guard).

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
        --smoke --steps 20 --agents 4

``--layers`` cuts a published config in depth (widths untouched); the
one-chip cut of h2o-danube-1.8b is ``CHIP_TRAIN`` in its config module.

``run_training`` is the importable entry point (used by the golden-run
regression harness, see benchmarks/regress.py): same seed -> same data
stream, same init, same trajectories.
"""
import argparse
import os
import sys


def build_trainer(arch: str = "h2o-danube-1.8b", smoke: bool = True,
                  layers: int = 0, agents: int = 2, seq: int = 128,
                  batch_per_agent: int = 2, optimizer: str = "frodo",
                  alpha: float = 0.02, beta: float = 0.008,
                  lam: float = 0.15, T: int = 40,
                  memory_mode: str = "exact", K: int = 8,
                  acc_dtype: str = "float32", use_kernel: bool = False,
                  topology: str = "complete", consensus_interval: int = 1,
                  collect_metrics: bool = False, mesh=None, **trainer_kw):
    """The ``Trainer`` that ``run_training`` drives, built without touching
    a device (so a test can lower its step for a described chip).

    ``smoke`` picks the arch's reduced CPU config; otherwise the published
    config, cut to ``layers`` layers by ``registry.reduced_layers`` when
    ``layers`` is set, every width untouched.  ``mesh`` (a ``Mesh`` with a
    ``data`` axis) spreads the agents over its devices."""
    from repro.configs import registry as REG
    from repro.training.trainer import Trainer
    from repro.training.train_step import TrainConfig

    cfg = REG.get_smoke_config(arch) if smoke else REG.get_config(arch)
    if layers:
        cfg = REG.reduced_layers(cfg, layers)
    tc = TrainConfig(optimizer=optimizer, alpha=alpha, beta=beta,
                     lam=lam, T=T, memory_mode=memory_mode, K=K,
                     acc_dtype=acc_dtype, use_kernel=use_kernel,
                     remat=not smoke, topology=topology,
                     consensus_interval=consensus_interval,
                     collect_metrics=collect_metrics)
    return Trainer(cfg, tc, n_agents=agents, mesh=mesh,
                   tokens_per_step=agents * batch_per_agent * seq,
                   **trainer_kw)


def run_training(steps: int = 20, agents: int = 2, seq: int = 128,
                 batch_per_agent: int = 2, mesh: bool = False,
                 ckpt_dir: str = "checkpoints", metrics_out: str = "",
                 collect_metrics: bool = False, seed: int = 0,
                 profile_dir: str = "", profile_start: int = 0,
                 profile_stop: int = 4, spans_out: str = "", **model_kw):
    """Run the training loop; returns ``(trainer, state)``: the trainer
    (history attached) and the final train state.

    ``model_kw`` are ``build_trainer``'s model and optimizer arguments
    (``arch``, ``smoke``, ``layers``, ``memory_mode``, ...); ``mesh`` puts
    the agents over every device of the host
    (``launch/mesh.py:make_host_mesh``).

    ``seed`` threads through both the parameter init and the synthetic
    token pipeline, so a fixed seed gives deterministic loss/grad-norm
    trajectories (the launch-train golden baseline relies on this).

    ``profile_dir`` turns on a programmatic ``jax.profiler`` capture over
    steps ``[profile_start, profile_stop]`` — the ``trace_scope`` tags,
    the ``StepTraceAnnotation`` and the host spans land in a real device
    trace there.  ``spans_out`` also records the host-side phase spans
    (``train.data`` / ``train.device_step`` / ``train.metrics`` /
    ``train.log``) and writes them as a Chrome trace-event file for
    Perfetto / ``repro.obs.report``.
    """
    from repro import obs
    from repro.data.synthetic import TokenPipeline, augment_modalities
    from repro.launch.mesh import make_host_mesh

    sink = obs.JsonlSink(metrics_out) if metrics_out else None
    trainer = build_trainer(
        agents=agents, seq=seq, batch_per_agent=batch_per_agent,
        collect_metrics=collect_metrics or bool(metrics_out),
        mesh=make_host_mesh() if mesh else None, ckpt_dir=ckpt_dir,
        log_every=5, sink=sink, profile_dir=profile_dir or None,
        profile_start=profile_start, profile_stop=profile_stop, **model_kw)
    state = trainer.init(seed=seed)
    data = augment_modalities(
        iter(TokenPipeline(vocab=trainer.cfg.vocab, seq_len=seq,
                           batch_per_agent=batch_per_agent,
                           n_agents=agents, seed=seed)), trainer.cfg)
    recorder = obs.SpanRecorder() if spans_out else None
    prev = obs.set_recorder(recorder) if recorder is not None else None
    try:
        state = trainer.run(state, data, steps)
    finally:
        if recorder is not None:
            obs.set_recorder(prev)
            recorder.save(spans_out, process_name="repro.launch.train")
        if sink is not None:
            sink.close()
    return trainer, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the published config to this many layers "
                         "(widths untouched); 0 keeps its depth")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--agents", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch-per-agent", type=int, default=2)
    ap.add_argument("--optimizer", default="frodo")
    ap.add_argument("--alpha", type=float, default=0.02)
    ap.add_argument("--beta", type=float, default=0.008)
    ap.add_argument("--lam", type=float, default=0.15)
    ap.add_argument("--T", type=int, default=40)
    ap.add_argument("--memory-mode", default="exact",
                    choices=("exact", "expsum"))
    ap.add_argument("--K", type=int, default=8,
                    help="exponentials of the expsum memory")
    ap.add_argument("--acc-dtype", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--use-kernel", action="store_true",
                    help="exact mode: fused Pallas update (needs a TPU); "
                         "the expsum mode's is fused on any TPU")
    ap.add_argument("--topology", default="complete")
    ap.add_argument("--consensus-interval", type=int, default=1)
    ap.add_argument("--force-devices", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="spread the agents over every device of the host")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds init + data stream (deterministic run)")
    ap.add_argument("--metrics-out", default="",
                    help="JSONL path for per-step telemetry (implies "
                         "--collect-metrics)")
    ap.add_argument("--collect-metrics", action="store_true",
                    help="compute consensus_error/memory_norm/... in-step")
    ap.add_argument("--profile-dir", default="",
                    help="jax.profiler capture dir (device trace over the "
                         "--profile-start..--profile-stop step window)")
    ap.add_argument("--profile-start", type=int, default=0)
    ap.add_argument("--profile-stop", type=int, default=4)
    ap.add_argument("--spans-out", default="",
                    help="write host-side phase spans as a Chrome trace "
                         "JSON (open in Perfetto)")
    args = ap.parse_args()

    if args.force_devices and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.force_devices}")
        os.execv(sys.executable, [sys.executable] + sys.argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run_training(arch=args.arch, smoke=args.smoke, layers=args.layers,
                 steps=args.steps, agents=args.agents, seq=args.seq,
                 batch_per_agent=args.batch_per_agent,
                 optimizer=args.optimizer, alpha=args.alpha, beta=args.beta,
                 lam=args.lam, T=args.T, memory_mode=args.memory_mode,
                 K=args.K, acc_dtype=args.acc_dtype,
                 use_kernel=args.use_kernel, topology=args.topology,
                 consensus_interval=args.consensus_interval,
                 mesh=args.mesh,
                 ckpt_dir=args.ckpt_dir, metrics_out=args.metrics_out,
                 collect_metrics=args.collect_metrics, seed=args.seed,
                 profile_dir=args.profile_dir,
                 profile_start=args.profile_start,
                 profile_stop=args.profile_stop, spans_out=args.spans_out)


if __name__ == "__main__":
    main()
