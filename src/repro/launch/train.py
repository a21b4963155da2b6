"""Production training launcher.

    python -m repro.launch.train --arch llama3.2-3b --shape train_4k \
        --steps 1000 --ckpt-dir /ckpts/run1 [--multi-pod] [--dry-run]

On a real TPU pod each host runs this binary (jax.distributed initialises
from the TPU environment); in this CPU container ``--test-mesh`` runs a
reduced config end-to-end and ``--dry-run`` lowers the full config against
the production mesh without allocating.
"""

from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--heartbeat-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile only (sets 512 host devices)")
    ap.add_argument("--test-mesh", action="store_true",
                    help="reduced config on the local devices")
    ap.add_argument("--distributed", action="store_true",
                    help="initialise jax.distributed from the environment")
    args = ap.parse_args()

    if args.dry_run:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count"
                              "=512").strip()

    import jax

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.distributed:
        jax.distributed.initialize()

    from repro.configs import ARCHS, SHAPES
    from repro.models.model import build_model, reduce_config
    from repro.optim import make_optimizer
    from repro.train.trainer import Trainer, TrainerConfig
    from repro.launch.mesh import make_production_mesh, make_test_mesh

    cfg = ARCHS[args.arch]
    shape = SHAPES[args.shape]
    assert shape.kind == "train", "use repro.launch.serve for serving shapes"

    if args.dry_run:
        from repro.launch.dryrun import run_cell, RESULTS
        rec = run_cell(args.arch, args.shape, args.multi_pod, RESULTS,
                       force=True, microbatches=args.microbatches)
        print(rec["status"], rec.get("memory_analysis"))
        return

    if args.test_mesh:
        cfg = reduce_config(cfg)
        mesh = make_test_mesh(model=1)
        import dataclasses
        shape = dataclasses.replace(shape, seq_len=64, global_batch=8)
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    model = build_model(cfg)
    opt = make_optimizer("adamw")
    tcfg = TrainerConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        heartbeat_dir=args.heartbeat_dir,
        host_id=jax.process_index(), n_hosts=jax.process_count())
    trainer = Trainer(model, opt, mesh, shape, tcfg,
                      microbatches=args.microbatches)
    out = trainer.run()
    print(f"final loss: {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
