"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \
        --steps 50
    # on a real slice: jax.distributed.initialize() is called when
    # JAX_COORDINATOR_ADDRESS is set, and the production mesh is used.
"""

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="pods,data,model (elastic override)")
    args = ap.parse_args()

    from ..compile_cache import enable_compile_cache
    enable_compile_cache()

    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        import jax
        jax.distributed.initialize()

    from ..configs import get
    from ..distributed import sharding as shd
    from ..training import AdamWConfig, DataConfig, TrainConfig, Trainer
    import jax

    cfg_m = get(args.arch)
    if args.smoke:
        cfg_m = cfg_m.reduced()

    tc = TrainConfig(
        model=cfg_m,
        opt=AdamWConfig(lr=3e-4, warmup_steps=min(20, args.steps // 5 + 1),
                        total_steps=args.steps),
        data=DataConfig(vocab_size=cfg_m.vocab_size, seq_len=args.seq,
                        global_batch=args.batch),
        n_steps=args.steps, checkpoint_dir=args.ckpt_dir)

    n_dev = len(jax.devices())
    if args.mesh:
        from .mesh import make_mesh
        pods, data, model = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh(pods, data, model)
    elif n_dev >= 256:
        from .mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = None

    if mesh is not None:
        with shd.use_mesh(mesh):
            trainer = Trainer(tc, mesh=mesh)
            report = trainer.run()
    else:
        trainer = Trainer(tc)
        report = trainer.run()
    for h in report["logged"][-5:]:
        print(h)
    print(f"steps={report['steps']} restarts={report['restarts']}")


if __name__ == "__main__":
    main()
