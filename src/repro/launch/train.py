"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt

``--smoke`` uses the reduced config on local devices (this container);
omit it on a real pod to train the full config on the production mesh
(mesh/shardings come from the same rule tables the dry-run proves out).
On multi-host pods, run one process per host (jax.distributed
initializes from the TPU environment) with identical flags.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.core import VPE
from repro.data import DataConfig, SyntheticStream
from repro.distributed import sharding as shardlib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.optim import adamw
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig, init_opt_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config, local devices")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--no-vpe", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
        mesh = None
        shardings = None
        batch_sharding = None
    else:
        mesh = make_production_mesh()
        params_av = model_lib.param_specs(cfg)
        shardings = None  # derived after init below
        batch_sharding = None

    data = SyntheticStream(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed))

    loop_cfg = TrainLoopConfig(
        total_steps=args.steps, peak_lr=args.lr,
        warmup_steps=max(args.steps // 10, 1),
        checkpoint_every=args.ckpt_every, checkpoint_dir=args.ckpt,
        num_microbatches=args.microbatches,
        compress_grads=args.compress_grads,
        enable_vpe=not args.no_vpe,
        log_every=max(args.steps // 20, 1),
    )
    loop = TrainLoop(cfg, loop_cfg, data, rng=jax.random.PRNGKey(args.seed))
    if mesh is not None:
        p_sh = shardlib.param_shardings(loop.params, mesh)
        o_sh = shardlib.param_shardings(loop.opt_state, mesh)
        loop.params = jax.tree.map(jax.device_put, loop.params, p_sh)
        loop.opt_state = jax.tree.map(jax.device_put, loop.opt_state, o_sh)
        loop.shardings = {"params": p_sh, "opt": o_sh}
        from jax.sharding import NamedSharding
        loop.batch_sharding = NamedSharding(mesh, shardlib.batch_spec(mesh))
    if args.resume and loop.restore():
        print(f"resumed from step {loop.step}")
    metrics = loop.run()
    print(f"done: {loop.step} steps; "
          f"loss {metrics[0]['loss']:.4f} -> {metrics[-1]['loss']:.4f}")
    print(loop.vpe.report())
    if args.ckpt:
        loop.save()


if __name__ == "__main__":
    main()
