"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 100 --batch 16 --seq 128 [--reduced] [--devices 8]

``--devices N`` forces N host devices and jits the step with the production
sharding rules on a (data × model) mesh — the single-process rehearsal of the
multi-pod launch (real pods: same code under jax.distributed.initialize).
"""

import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default=None, help="e.g. 4x2 (data x model)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )

    import jax
    from repro.configs import get_config
    from repro.models.config import reduced as reduce_cfg

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)

    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((d, m), ("data", "model"))
        from repro.distributed.sharding import batch_pspec, param_pspecs, to_shardings
        from repro.train.steps import init_train_state, make_train_step
        from repro.train.optimizer import AdamWState
        from repro.train.steps import TrainState
        from repro.data.loader import batches
        from jax.sharding import PartitionSpec as P

        with jax.set_mesh(mesh):
            state = init_train_state(cfg, jax.random.PRNGKey(0))
            pspecs = param_pspecs(state.params, mesh, False)
            sspecs = TrainState(params=pspecs,
                                opt=AdamWState(step=P(), mu=pspecs, nu=pspecs))
            state = jax.device_put(state, to_shardings(sspecs, mesh))
            step_fn = jax.jit(
                make_train_step(cfg, base_lr=args.lr, total_steps=args.steps,
                                microbatches=args.microbatches),
                in_shardings=(to_shardings(sspecs, mesh), None),
            )
            for step, batch in batches(cfg, args.batch, args.seq):
                if step >= args.steps:
                    break
                state, metrics = step_fn(state, batch)
                if step % 10 == 0:
                    print(f"step {step}: loss={float(metrics['loss']):.4f}")
        return

    from repro.train.trainer import train

    train(cfg, steps=args.steps, global_batch=args.batch, seq_len=args.seq,
          lr=args.lr, ckpt_dir=args.ckpt_dir, microbatches=args.microbatches)


if __name__ == "__main__":
    sys.exit(main())
