"""Launcher: real training of any assigned architecture on the local mesh.

On this CPU container it trains the *reduced* variants (one device); on a
TPU slice the same entry point builds the production mesh and shards per
:mod:`repro.dist.sharding`.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --reduced --steps 30 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.data import DataPipeline, synthetic_lm_dataset
from repro.kernels.ops import KERNEL_STATS
from repro.dist.sharding import (ShardingRules, batch_specs, mesh_sizes_of,
                                 param_specs)
from repro.launch.specs import batch_struct
from repro.models import LM
from repro.train.optimizer import init_opt_state
from repro.train.step import build_train_step, shardings_for
from repro.utils.compile_cache import enable_compile_cache


def local_mesh():
    n = len(jax.devices())
    # largest (data, model) factorization of the local device count
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0:
            model = m
            break
    # Auto axes: the compiler propagates placements from the committed
    # params and batch.  Explicit axes (jax.make_mesh's default) type every
    # op's output sharding and refuse the embedding gather, whose operands
    # put ``data`` on both the batch and the d_model dimension.
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--use-kernel", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(d_model=256)
    if cfg.frontend != "none":
        raise SystemExit(f"{args.arch} needs frontend embeddings; use the "
                         "dry-run for its full pipeline")

    mesh = local_mesh()
    rules = ShardingRules(fsdp="data", tp="model", dp=("data",))
    model = LM(cfg, use_kernel=args.use_kernel)
    print(f"training {cfg.name} ({cfg.param_count()/1e6:.1f}M params) on "
          f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state("adamw", params)
    data = DataPipeline(
        synthetic_lm_dataset(4096, args.seq, cfg.vocab_size), args.batch)

    ns = lambda t: shardings_for(mesh, t)
    sizes = mesh_sizes_of(mesh)     # gate divisibility on the live mesh
    pshard = ns(param_specs(jax.eval_shape(lambda: params), rules, sizes))
    params = jax.device_put(params, pshard)
    opt = jax.device_put(opt, ns(param_specs(jax.eval_shape(lambda: opt),
                                             rules, sizes)))
    bshard = ns(batch_specs(cfg, batch_struct(cfg, args.batch, args.seq),
                            rules, sizes))

    # XLA:CPU has no buffer donation (and warns per call) — gate it off
    donate = (0, 1) if jax.default_backend() != "cpu" else ()
    step_fn = jax.jit(build_train_step(model), donate_argnums=donate)
    t0 = time.time()
    for i in range(args.steps):
        batch = jax.device_put(
            {k: jnp.asarray(v) for k, v in data.next_batch().items()}, bshard)
        params, opt, loss = step_fn(params, opt, batch,
                                    jnp.float32(args.lr), jnp.int32(i))
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}  "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    print(f"done: {args.steps} steps in {time.time()-t0:.1f}s; "
          f"final loss {float(loss):.4f}")
    if args.use_kernel:
        print(f"kernel plane: {KERNEL_STATS.calls} call sites, "
              f"{KERNEL_STATS.fallbacks} fallbacks")


if __name__ == "__main__":
    main()
