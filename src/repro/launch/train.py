"""Training launcher — a thin CLI over :class:`repro.api.Session`.

On the CPU (``JAX_PLATFORMS=cpu``) this runs the REDUCED variants on a
small forced-host mesh, with the Pallas kernels interpreted. On a TPU the
kernels compile to Mosaic and ``--full`` selects the published widths;
the mesh must fit the chips present, so one chip needs ``--mesh 1``.
``chip_smoke.py`` at the repo root is what has run on a TPU v5e chip:
``distill_fl`` over the full flad-adllm, and ``pipeline`` over the full
flad-vision on a (2, 2) mesh of four chips. The FHDP strategy is the
paper's system; ``tensor`` is
the datacenter-style baseline; ``fedavg``/``fl_pipeline`` run FedAvg
rounds instead of steps. All wiring (mesh, devices, strategy, hooks)
lives in :mod:`repro.api` — this file only parses flags.

  PYTHONPATH=src python -m repro.launch.train --arch flad-vision \
      --strategy pipeline --steps 50 --devices 8 --mesh 2,4
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="flad-vision")
    ap.add_argument("--shape", default=None, help="named shape or 'SEQxBATCH'")
    ap.add_argument("--strategy", default="pipeline",
                    choices=["tensor", "pipeline", "fedavg", "fl_pipeline",
                             "swift_pipeline", "hier_fl", "async_hier_fl",
                             "distill_fl"])
    ap.add_argument("--steps", type=int, default=50,
                    help="train steps (FL strategies: rounds)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--local-steps", type=int, default=1,
                    help="local steps per FL round (fedavg/fl_pipeline)")
    ap.add_argument("--fleet", default="nano*4,agx*2",
                    help="heterogeneous fleet spec for swift_pipeline, "
                         "e.g. 'nano*4,nx*2,agx'")
    ap.add_argument("--topology", default="2@nano*2,agx*2",
                    help="hier_fl vehicle->edge->cloud topology: "
                         "'E@FLEET', e.g. '2@nano*2,agx*2' = 2 edge pods "
                         "over that fleet")
    ap.add_argument("--codec", default="none",
                    choices=["none", "int8", "topk"],
                    help="hier_fl uplink codec (update compression)")
    ap.add_argument("--async-decay", type=float, default=None,
                    help="hier_fl: staleness decay per missed round "
                         "deadline (enables the predicted-staleness "
                         "merge); async_hier_fl: the observed-staleness "
                         "decay (default 0.5)")
    ap.add_argument("--async-clock", type=float, default=None,
                    help="async_hier_fl: cloud merge period in simulated "
                         "seconds (default: infinite deadline — the "
                         "synchronous special case)")
    ap.add_argument("--migrate-every", type=float, default=None,
                    help="async_hier_fl: simulated seconds per mobility "
                         "step; vehicles migrate between edge pods when "
                         "they leave their pod's comm radius")
    ap.add_argument("--compute-jitter", type=float, default=0.0,
                    help="async_hier_fl: per-(vehicle, round) uniform "
                         "compute slowdown fraction")
    ap.add_argument("--lora-rank", type=int, default=4,
                    help="distill_fl: LoRA rank of the per-pod adapters")
    ap.add_argument("--kd-weight", type=float, default=0.3,
                    help="distill_fl: weight of the teacher-distillation "
                         "terms in the student loss")
    ap.add_argument("--mix", type=float, default=0.5,
                    help="distill_fl: per-round blend toward the cloud "
                         "merge (1 = global FedAvg-of-adapters, 0 = "
                         "fully local per-pod adapters)")
    ap.add_argument("--distill-warmup", type=int, default=20,
                    help="distill_fl: supervised warmup steps for the "
                         "cloud AD-LLM before it freezes as the teacher")
    ap.add_argument("--depart", default=None, metavar="STEP:VID",
                    help="swift_pipeline: simulate vehicle VID departing "
                         "after step STEP (live template repartition)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU testing)")
    ap.add_argument("--mesh", default="2,4", help="data,model (or pod,data,model)")
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (TPU scale)")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="async_hier_fl: write a Perfetto-loadable "
                         "sim-time trace (repro.obs) to PATH")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write a repro.obs metrics-registry snapshot "
                         "(JSON) to PATH")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.api import LoopHooks, MeshSpec, Session
    from repro.launch.compile_cache import use_compile_cache
    from repro.recovery.backup import EdgeBackup

    use_compile_cache()

    options = {}
    fl = args.strategy in ("fedavg", "fl_pipeline", "hier_fl",
                           "async_hier_fl", "distill_fl")
    if fl:
        options["local_steps"] = args.local_steps
    if args.strategy == "swift_pipeline":
        options["fleet"] = args.fleet
    if args.strategy == "hier_fl":
        options.update(topology=args.topology, codec=args.codec,
                       async_decay=args.async_decay)
    if args.strategy == "async_hier_fl":
        options.update(topology=args.topology, codec=args.codec,
                       clock=args.async_clock,
                       migrate_every=args.migrate_every,
                       compute_jitter=args.compute_jitter)
        if args.async_decay is not None:
            options["decay"] = args.async_decay
    if args.strategy == "distill_fl":
        options.update(topology=args.topology, codec=args.codec,
                       async_decay=args.async_decay,
                       lora_rank=args.lora_rank,
                       kd_weight=args.kd_weight, mix=args.mix,
                       warmup_steps=args.distill_warmup)
    session = Session(
        args.arch, full=args.full, shape=args.shape,
        mesh=MeshSpec.parse(args.mesh, devices=args.devices or None),
        strategy=args.strategy, learning_rate=args.lr, seed=args.seed,
        hooks=LoopHooks(log_every=1 if fl else 10,
                        backup=EdgeBackup(interval=10),
                        checkpoint_path=args.checkpoint,
                        checkpoint_every=50 if args.checkpoint else 0),
        **options)
    if args.depart:
        if args.strategy != "swift_pipeline":
            raise SystemExit("--depart requires --strategy swift_pipeline")
        import dataclasses

        from repro.recovery.recover import Repartitioner
        step_s, vid_s = args.depart.split(":")
        session.hooks = dataclasses.replace(
            session.hooks,
            repartition=Repartitioner(session, {int(step_s): int(vid_s)}))
    out = session.run(args.steps, trace=args.trace, metrics=args.metrics)
    last = out["history"][-1]
    print(f"[train] done: {last}")
    if args.trace:
        print(f"[train] trace written to {out['trace_path']} "
              f"(load at https://ui.perfetto.dev)")
    if args.metrics:
        print(f"[train] metrics snapshot written to {out['metrics_path']}")


if __name__ == "__main__":
    main()
