"""Serving launcher: synthetic traffic through the batching scheduler.

Drives ``serving.scheduler.Scheduler`` with a seeded Poisson arrival
process — request arrivals, prompt lengths, and generation lengths are all
drawn from one ``numpy`` generator, and time is measured in *scheduler
steps*, so a given ``--seed`` always produces the same admission trace and
(greedy decode being deterministic) the same tokens:

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m \
        --smoke --requests 8 --rate 0.7 --seed 0

``--batch`` switches to the legacy one-shot mode (a single
``Engine.generate`` call over a fixed batch).
"""
import argparse
from typing import Any, Dict, Optional


def run_traffic(arch: str = "mamba2-780m", smoke: bool = True,
                n_requests: int = 8, rate: float = 0.7,
                prompt_len_range=(4, 12), new_tokens_range=(3, 8),
                max_slots: int = 4, prefill_chunk: int = 8,
                token_budget: int = 32, max_len: int = 64,
                seed: int = 0, metrics_out: Optional[str] = None,
                quiet: bool = False, profile_dir: str = "",
                profile_start: int = 0, profile_stop: int = 4,
                spans_out: str = "") -> Dict[str, Any]:
    """Seeded Poisson-arrival workload; returns a summary dict.

    Per scheduler step, ``Poisson(rate)`` new requests arrive (capped at
    ``n_requests`` total); each draws its prompt tokens, prompt length, and
    ``max_new`` from the same generator.  ``metrics_out`` captures the full
    ``serve.step`` / ``serve.request`` telemetry stream as JSONL (each
    ``serve.step`` row carries the per-phase ``phase_*_ms`` split —
    ``python -m repro.obs.report`` renders the breakdown).

    ``profile_dir`` captures a ``jax.profiler`` device trace over
    scheduler steps ``[profile_start, profile_stop]``; ``spans_out``
    writes the host-side phase spans as a Perfetto-loadable Chrome trace.
    """
    import jax
    import numpy as np
    from repro import obs
    from repro.configs import registry as REG
    from repro.models import transformer as T
    from repro.serving.scheduler import Scheduler, SchedulerConfig

    cfg = REG.get_smoke_config(arch) if smoke else REG.get_config(arch)
    params = T.init_params(jax.random.key(seed), cfg)
    sink = obs.JsonlSink(metrics_out) if metrics_out else obs.MemorySink()
    sch = Scheduler(cfg, params,
                    SchedulerConfig(max_slots=max_slots, max_len=max_len,
                                    prefill_chunk=prefill_chunk,
                                    token_budget=token_budget), sink=sink)
    rng = np.random.default_rng(seed)
    rids = []
    n_submitted = 0
    max_occ = 0
    max_queue = 0
    prof = obs.ProfileWindow(profile_dir or None, profile_start,
                             profile_stop)
    recorder = obs.SpanRecorder() if spans_out else None
    prev = obs.set_recorder(recorder) if recorder is not None else None
    try:
        while n_submitted < n_requests or sch.has_work:
            if n_submitted < n_requests:
                for _ in range(int(rng.poisson(rate))):
                    if n_submitted >= n_requests:
                        break
                    plen = int(rng.integers(*prompt_len_range,
                                            endpoint=True))
                    n_new = int(rng.integers(*new_tokens_range,
                                             endpoint=True))
                    prompt = rng.integers(1, cfg.vocab,
                                          plen).astype(np.int32)
                    frames = None
                    if cfg.family == "audio":
                        frames = rng.normal(size=(cfg.n_frames, cfg.d_model)
                                            ).astype(np.float32)
                    rids.append(sch.submit(prompt, n_new, frames=frames))
                    n_submitted += 1
            if sch.has_work:
                prof.maybe_start(sch.step_idx)
                rec = sch.step()
                prof.maybe_stop(rec["step"])
                max_occ = max(max_occ, rec["occupancy"])
                max_queue = max(max_queue, rec["queue_depth"])
    finally:
        prof.close()
        if recorder is not None:
            obs.set_recorder(prev)
            recorder.save(spans_out, process_name="repro.launch.serve")
    if metrics_out:
        sink.close()
    reqs = [sch.done[r] for r in rids]
    total_new = sum(len(r.tokens) for r in reqs)
    summary = {
        "arch": arch, "seed": seed, "n_requests": n_requests,
        "total_steps": sch.step_idx, "total_new_tokens": total_new,
        "max_occupancy": max_occ, "max_queue_depth": max_queue,
        "mean_ttft_steps": round(
            float(np.mean([r.first_token_step - r.submit_step + 1
                           for r in reqs])), 3),
        "decode_tokens_per_s": round(total_new / max(sch.decode_s, 1e-9), 1),
    }
    if not quiet:
        for r in reqs:
            print(f"req{r.rid}: prompt_len={r.prompt_len} "
                  f"tokens={r.output().tolist()}")
        print(summary)
    return summary


def _run_static(args) -> None:
    """Legacy one-shot mode: a single batched generate."""
    import jax
    import numpy as np
    from repro.configs import registry as REG
    from repro.models import transformer as T
    from repro.serving.engine import Engine

    cfg = (REG.get_smoke_config(args.arch) if args.smoke
           else REG.get_config(args.arch))
    params = T.init_params(jax.random.key(args.seed), cfg)
    eng = Engine(cfg, params, max_len=args.max_len)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(1, cfg.vocab,
                           (args.batch, args.prompt_len)).astype(np.int32)
    frames = None
    if cfg.family == "audio":
        frames = rng.normal(size=(args.batch, cfg.n_frames,
                                  cfg.d_model)).astype(np.float32)
    out = eng.generate(prompts, n_new=args.new_tokens, frames=frames)
    for i, row in enumerate(out):
        print(f"req{i}: {row.tolist()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-780m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-len", type=int, default=64)
    # traffic mode (default)
    ap.add_argument("--requests", type=int, default=8,
                    help="total synthetic requests to issue")
    ap.add_argument("--rate", type=float, default=0.7,
                    help="Poisson arrival rate (requests per scheduler step)")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--token-budget", type=int, default=32)
    ap.add_argument("--metrics-out", default=None,
                    help="write serve.step/serve.request JSONL here")
    ap.add_argument("--profile-dir", default="",
                    help="jax.profiler capture dir (device trace over the "
                         "--profile-start..--profile-stop step window)")
    ap.add_argument("--profile-start", type=int, default=0)
    ap.add_argument("--profile-stop", type=int, default=4)
    ap.add_argument("--spans-out", default="",
                    help="write host-side phase spans as a Chrome trace "
                         "JSON (open in Perfetto)")
    # legacy one-shot mode
    ap.add_argument("--batch", type=int, default=None,
                    help="run one static Engine.generate over this batch "
                         "size instead of the traffic driver")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.batch is not None:
        _run_static(args)
    else:
        run_traffic(arch=args.arch, smoke=args.smoke,
                    n_requests=args.requests, rate=args.rate,
                    max_slots=args.max_slots,
                    prefill_chunk=args.prefill_chunk,
                    token_budget=args.token_budget, max_len=args.max_len,
                    seed=args.seed, metrics_out=args.metrics_out,
                    profile_dir=args.profile_dir,
                    profile_start=args.profile_start,
                    profile_stop=args.profile_stop,
                    spans_out=args.spans_out)


if __name__ == "__main__":
    main()
