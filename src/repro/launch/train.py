"""Training launcher: config-driven entry point.

Single-host CPU demo:
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --reduced \
      --steps 50 --policy ff_master

On a real multi-host TPU deployment the same entry point runs under
``jax.distributed.initialize()`` (one process per host); the data pipeline
shards by host id and the mesh comes from ``make_production_mesh``.
"""

import argparse
import os

_f = os.environ.get("XLA_FLAGS", "")
if "--xla_cpu_max_isa" not in _f:
    os.environ["XLA_FLAGS"] = ("--xla_cpu_max_isa=SSE4_2 " + _f).strip()

import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size variant (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--policy", default="ff_master")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", action="store_true",
                    help="build the local device mesh and route the step's "
                         "loss/grad reductions through the mesh-partitioned "
                         "FF tier (compensated cross-device combines)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config
    from repro.core.policy import PrecisionPolicy
    from repro.core.selfcheck import require_eft_safe
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import init_params
    from repro.optim.adamw import AdamW, cosine_schedule
    from repro.train.train_step import make_train_step
    from repro.train.trainer import Trainer, TrainerConfig

    require_eft_safe()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    policy = PrecisionPolicy.make(args.policy,
                                  compute_dtype=cfg.compute_dtype)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[train] {cfg.name}: {n/1e6:.1f}M params, policy={policy.level}")

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_local_data_mesh
        mesh = make_local_data_mesh()
        print(f"[train] mesh: {dict(mesh.shape)} — FF reductions are "
              f"mesh-partitioned (repro.ff.sharded)")
    opt = AdamW(learning_rate=cosine_schedule(args.lr, 10, args.steps),
                ff=policy.ff_master_weights)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, policy, opt,
                                      microbatches=args.microbatches,
                                      mesh=mesh),
                      donate_argnums=(0, 1))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch))

    def data_iter(i):
        return {k: jnp.asarray(v) for k, v in data.batch(i).items()}

    trainer = Trainer(
        TrainerConfig(total_steps=args.steps,
                      ckpt_every=max(args.steps // 3, 1),
                      ckpt_dir=args.ckpt_dir, log_every=10),
        step_fn, params, opt_state, data_iter)
    if args.ckpt_dir:
        trainer.restore()
    print(f"[train] done: {trainer.run()}")


if __name__ == "__main__":
    main()
