"""Serving launcher (paper Fig. 2 inference procedure) — a thin CLI over
:meth:`repro.api.Session.serve`.

Vehicles send vision-encoder features to the edge; the edge AD-LLM
prefills the feature+instruction context and decodes waypoint tokens /
regresses waypoints, returned to the vehicle's PID controller. The
batched prefill/decode driver lives in :mod:`repro.api.serving`; the
paged-KV continuous-batching tier (``--scheduler continuous``) lives in
:mod:`repro.serve`.

  PYTHONPATH=src python -m repro.launch.serve --arch flad-adllm \
      --batch 8 --decode-steps 16
  PYTHONPATH=src python -m repro.launch.serve --arch flad-adllm \
      --scheduler continuous --slots 4 --cache int8 --fleet nano*2,agx*2
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="flad-adllm")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--context", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=16,
                    help="decode steps per batch (legacy scheduler)")
    ap.add_argument("--requests", type=int, default=3,
                    help="request batches (legacy) / trace length "
                         "(continuous)")
    ap.add_argument("--scheduler", choices=("legacy", "continuous"),
                    default="legacy")
    ap.add_argument("--slots", type=int, default=0,
                    help="continuous-batching lanes (default: --batch)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="KV block size in tokens (continuous)")
    ap.add_argument("--cache", choices=("fp32", "int8"), default="fp32",
                    help="paged KV-cache storage mode (continuous)")
    ap.add_argument("--prefill", choices=("chunked", "monolithic"),
                    default="chunked",
                    help="prompt prefill path: paged chunks interleaved "
                         "with decode, or the bucketed monolithic "
                         "baseline (continuous)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="tokens per prefill chunk (continuous, chunked)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share pod prompt-prefix KV blocks across "
                         "requests (continuous, chunked prefill only)")
    ap.add_argument("--fleet", default="nano*2,agx*2",
                    help="vehicle fleet spec for the load generator "
                         "(continuous)")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-verify speculative decoding (continuous, "
                         "greedy; streams stay bit-identical)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens proposed per lane per step "
                         "(with --speculative)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto-loadable sim-time trace of "
                         "the final warm pass to PATH (continuous)")
    ap.add_argument("--sampling", choices=("greedy", "temperature"),
                    default="greedy")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.api import MeshSpec, Session
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    session = Session(args.arch, full=args.full, strategy="tensor",
                      seed=args.seed,
                      mesh=MeshSpec((1,), axes=("data",),
                                    devices=args.devices or 0))
    kw = {}
    if args.scheduler == "continuous":
        kw = dict(block_size=args.block_size, cache=args.cache,
                  fleet=args.fleet, prefill=args.prefill,
                  prefill_chunk=args.prefill_chunk,
                  prefix_cache=args.prefix_cache, trace=args.trace,
                  speculative=args.speculative, draft_k=args.draft_k)
    elif args.trace:
        raise SystemExit("--trace requires --scheduler continuous")
    elif args.speculative:
        raise SystemExit("--speculative requires --scheduler continuous")
    report = session.serve(requests=args.requests,
                           batch=args.slots or args.batch,
                           context=args.context,
                           decode_steps=args.decode_steps,
                           scheduler=args.scheduler, sampling=args.sampling,
                           temperature=args.temperature, **kw)
    if args.trace:
        print(f"[serve] trace written to {report['trace_path']} "
              f"(load at https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
