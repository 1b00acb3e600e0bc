"""Serving launcher: batched greedy decode against a (reduced or
checkpointed) model.

    PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b --smoke
"""

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    from ..compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..configs import get
    from ..models import build_model
    from ..serving import Engine, ServeConfig
    from ..training import checkpoint as ckpt

    cfg = get(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        trees, _ = ckpt.restore(args.ckpt_dir, {"params": params})
        params = trees["params"]
    engine = Engine(model, params, ServeConfig(
        max_new_tokens=args.new_tokens,
        max_cache_len=args.prompt_len + args.new_tokens + 8))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    out = engine.generate(prompts)
    for i, row in enumerate(np.asarray(out)):
        print(f"[{i}] {row.tolist()}")


if __name__ == "__main__":
    main()
