"""Serving driver: load (or init) a model, shard with SERVE_RULES, serve a
synthetic request stream through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b-smoke \
        --requests 8 --max-new 16
"""
import argparse

__all__ = ["main", "run"]


def run(argv=None) -> dict:
    """Serve a synthetic request stream; returns what was generated.

    Keys: ``arch``, ``mesh``, ``dtype``, ``vocab_size``, ``outputs`` (one
    int array of generated token ids per request) and ``wall_s`` (the serve
    loop, compiles included).
    """
    import time

    import jax
    import numpy as np

    from ..checkpoint import Checkpointer
    from ..compat import make_mesh
    from ..configs import get_config
    from ..models import get_model
    from ..serve import Engine, ServeConfig
    from ..sharding import SERVE_RULES, tree_shardings

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b-smoke")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a training checkpoint")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if cfg.name.endswith("-smoke"):
        cfg = cfg.replace(dtype="float32")
    model = get_model(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))

    params, pspecs = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        ck = Checkpointer(args.ckpt_dir)
        state, _ = ck.restore({"params": params})
        params = state["params"]
    shardings = tree_shardings(jax.eval_shape(lambda: params), pspecs,
                               SERVE_RULES, mesh)
    params = jax.tree.map(jax.device_put, params, shardings)

    eng = Engine(model, params, ServeConfig(max_len=args.max_len,
                                            slots=args.slots))
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32)
            for _ in range(args.requests)]
    t0 = time.perf_counter()
    outs = eng.serve(reqs, max_new=args.max_new)
    return {"arch": cfg.name, "mesh": args.mesh, "dtype": cfg.dtype,
            "vocab_size": cfg.vocab_size, "outputs": outs,
            "wall_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    """CLI entry: serve and print a one-line summary."""
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    res = run(argv)
    toks = sum(o.size for o in res["outputs"])
    dt = res["wall_s"]
    print(f"[serve] arch={res['arch']} mesh={res['mesh']} "
          f"requests={len(res['outputs'])} new_tokens={toks} wall={dt:.2f}s "
          f"throughput={toks/dt:.1f} tok/s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
