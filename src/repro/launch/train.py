"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --batch 4 --seq 32 --ckpt-dir /tmp/ckpt

Runs the full Trainer (data pipeline -> pjit train step -> checkpoints ->
watchdog). With --mesh data,model=RxC it builds a sharded mesh (requires the
matching --devices host-device override, set before jax initializes)."""
import argparse
import logging
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--async-save", action="store_true")
    ap.add_argument("--mesh", default="", help="e.g. '2x2' => (data,model) mesh")
    ap.add_argument("--devices", type=int, default=0, help="host device override")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    from repro.configs import get_arch
    from repro.data.pipeline import DataConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.train.step import TrainConfig
    from repro.train.trainer import TrainerConfig

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    data = DataConfig(batch=args.batch, seq_len=args.seq)
    tc = TrainConfig(
        lr=args.lr,
        total_steps=args.steps,
        warmup=max(args.steps // 10, 1),
        microbatches=args.microbatches,
        compress_grads=args.compress_grads,
    )
    tcfg = TrainerConfig(
        total_steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        async_save=args.async_save,
    )
    mesh = None
    if args.mesh:
        r, c = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((r, c), ("data", "model"))
    step, _, losses = train(cfg, data, tc, tcfg, mesh=mesh)
    print(f"finished at step {step}; loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return 0


def train(cfg, data, tc, tcfg, *, mesh=None, seed: int = 0):
    """Build the :class:`~repro.train.trainer.Trainer` and run it; returns
    ``(final_step, state, losses)``. With ``mesh`` the train state and the
    batches are placed by the ``dist.sharding`` rules and every step runs
    under ``use_mesh(mesh)``."""
    from repro.train.trainer import Trainer

    if mesh is None:
        return Trainer(cfg, data, tc, tcfg).run(seed=seed)

    import jax

    from repro.dist.sharding import batch_pspecs, to_named, use_mesh
    from repro.models.registry import batch_specs, build_model
    from repro.train.step import init_train_state, make_optimizer, train_state_pspecs

    with use_mesh(mesh):
        api = build_model(cfg)
        state_shapes = jax.eval_shape(
            lambda: init_train_state(
                api, make_optimizer(tc), jax.random.PRNGKey(seed),
                compress_grads=tc.compress_grads,
            )
        )
        state_sh = to_named(train_state_pspecs(state_shapes, mesh), mesh)
        batch_sh = to_named(
            batch_pspecs(batch_specs(cfg, data.batch, data.seq_len), mesh), mesh
        )
        trainer = Trainer(cfg, data, tc, tcfg, mesh=mesh,
                          state_shardings=state_sh, batch_shardings=batch_sh)
        return trainer.run(seed=seed)


if __name__ == "__main__":
    sys.exit(main())
