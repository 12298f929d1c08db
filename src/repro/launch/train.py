"""Distributed training driver.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b-smoke \
        --steps 20 --mesh 1x1

Builds the mesh, resolves TRAIN_RULES shardings for state and batch, applies
the activation-sharding context, and runs the fault-tolerant loop
(checkpointer + supervisor + straggler detector). On a real fleet this is
the per-process entry point (jax.distributed.initialize is invoked when the
standard cluster env vars are present); on a CPU it runs the smoke configs.

XLA flags for the TPU are not set here: none has been measured on the chip
yet.
"""
import argparse
import os

__all__ = ["main"]


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b-smoke")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM data x model mesh shape, e.g. 16x16")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="artifacts/train_ckpt")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    if "COORDINATOR_ADDRESS" in os.environ:       # multi-host fleet
        jax.distributed.initialize()

    from ..checkpoint import Checkpointer
    from ..configs import get_config
    from ..data import ByteCorpus, DataConfig
    from ..ft import StragglerDetector, Supervisor
    from ..models import Runtime, get_model
    from ..sharding import TRAIN_RULES, activation_sharding, tree_shardings
    from ..train import (OptConfig, TrainConfig, init_train_state,
                         make_train_step, train_loop)
    from ..train.optimizer import init_opt_state, opt_state_specs

    cfg = get_config(args.arch)
    model = get_model(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    from repro.compat import make_mesh
    mesh = make_mesh((d, m), ("data", "model"))

    tc = TrainConfig(opt=OptConfig(lr=args.lr, warmup_steps=10,
                                   total_steps=args.steps),
                     microbatches=args.microbatches,
                     runtime=Runtime(remat=args.remat), ckpt_every=50)
    data = ByteCorpus(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.global_batch,
        n_hosts=jax.process_count(), host_id=jax.process_index()))

    # Sharded state: resolve TRAIN_RULES onto the mesh for params + opt.
    state, pspecs = init_train_state(model, jax.random.PRNGKey(0), tc)
    ospecs = opt_state_specs(pspecs, tc.opt, has_master="master" in state["opt"])
    shardings = tree_shardings(
        jax.eval_shape(lambda: state), {"params": pspecs, "opt": ospecs},
        TRAIN_RULES, mesh)
    state = jax.tree.map(jax.device_put, state, shardings)

    def step_with_ctx(st, batch):
        with activation_sharding(mesh, TRAIN_RULES):
            return make_train_step(model, tc)(st, batch)

    step_fn = jax.jit(step_with_ctx, donate_argnums=0)
    ck = Checkpointer(args.ckpt_dir)
    straggler = StragglerDetector()

    start = 0
    if args.resume and ck.latest_step() is not None:
        state, _ = ck.restore(state)
        start = ck.latest_step()
        print(f"resumed from step {start}")

    def train_fn(st, st_step):
        return train_loop(model, tc, data, steps=args.steps, state=st,
                          start_step=st_step, checkpointer=ck,
                          step_fn=step_fn, straggler=straggler)

    sup = Supervisor(ck, max_restarts=3)
    state, hist = sup.run(lambda st, s0: train_fn(st, s0), state)

    losses = [mtr["loss"] for _, mtr in hist]
    print(f"[train] arch={cfg.name} mesh={args.mesh} steps={len(hist)} "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"restarts={sup.restarts} stragglers={len(straggler.flagged)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
