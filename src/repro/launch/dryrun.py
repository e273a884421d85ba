"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) case.

This is the proof that the distribution config is coherent without real
hardware: ``jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed
on the production meshes — 256-chip single-pod (16×16) and 512-chip
multi-pod (2×16×16) — for all 10 architectures × 4 input shapes (minus the
assignment-mandated skips).  ``memory_analysis()`` proves the state fits;
``cost_analysis()`` + the HLO collective scan feed §Roofline.

Usage::

    python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""

import argparse
import os
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (SHAPES, config_for_shape, get_config, list_archs,
                           shape_applicable)
from repro.dist.sharding import (ShardingRules, batch_specs, cache_specs,
                                 mesh_sizes_of, param_specs, seq_constrainer)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models.transformer import LM
from repro.train.optimizer import init_opt_state
from repro.train.step import (build_prefill_step, build_serve_step,
                              build_train_step, shardings_for)

__all__ = ["run_case", "main"]

_ns = shardings_for


def _collect(lowered, compiled) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        out["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float))}
    except Exception as e:  # pragma: no cover
        out["cost_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            out["memory"] = {
                k: int(getattr(ma, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover
        out["memory_error"] = repr(e)
    return out


def run_case(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules: Optional[ShardingRules] = None,
             collect_hlo: bool = True, verbose: bool = True,
             use_scan: bool = False,
             cfg_overrides: Optional[Dict[str, Any]] = None,
             tag: str = "", reduced: bool = False) -> Dict[str, Any]:
    """Lower + compile one (arch, shape, mesh) case; returns the record.

    ``reduced=True`` is the 1-device smoke path: the arch's reduced variant
    and a shrunk input shape compiled on a local (data=1, model=1) mesh —
    the structural proof that rules → specs → step wiring is coherent
    without 512 placeholder devices.
    """
    shape = SHAPES[shape_name]
    base = get_config(arch)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "multi_pod": multi_pod, "reduced": reduced}
    if not shape_applicable(base, shape):
        rec["status"] = "skipped"
        rec["reason"] = ("encoder-only: no decode step"
                         if base.is_encoder_only else "inapplicable")
        return rec

    cfg = config_for_shape(base, shape)
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, remat=True)
    if reduced:
        if multi_pod:
            raise ValueError("--reduced runs on the local single mesh")
        cfg = cfg.reduced()
        shape = dataclasses.replace(shape, global_batch=4, seq_len=64)
        collect_hlo = False
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
        rec["cfg_overrides"] = dict(cfg_overrides)
    rec["tag"] = tag
    rec["sliding_window"] = cfg.sliding_window

    mesh = (jax.make_mesh((1, 1), ("data", "model")) if reduced
            else make_production_mesh(multi_pod=multi_pod))
    sizes = mesh_sizes_of(mesh)
    rules = rules or ShardingRules.for_mesh(multi_pod)
    rec["rules"] = dataclasses.asdict(rules)
    # unroll → exact per-layer flop accounting (XLA counts a while body
    # once); scan → small HLO for the fast multi-pod sharding-proof pass
    model = LM(cfg, unroll=not use_scan, constrain=seq_constrainer(rules))
    rec["layer_scan"] = use_scan

    t0 = time.time()
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_specs(params_shape, rules, sizes)
    pshard = _ns(mesh, pspecs)
    scalar = NamedSharding(mesh, P())
    kind, kw = input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        if kind == "train":
            opt_shape = jax.eval_shape(
                lambda p: init_opt_state("adamw", p), params_shape)
            oshard = _ns(mesh, param_specs(opt_shape, rules, sizes))
            bshard = _ns(mesh, batch_specs(cfg, kw["batch"], rules, sizes))
            fn = build_train_step(model)
            jf = jax.jit(fn,
                         in_shardings=(pshard, oshard, bshard, scalar, scalar),
                         out_shardings=(pshard, oshard, scalar),
                         donate_argnums=(0, 1))
            lowered = jf.lower(params_shape, opt_shape, kw["batch"],
                               jax.ShapeDtypeStruct((), jnp.float32),
                               jax.ShapeDtypeStruct((), jnp.int32))
        elif kind == "prefill":
            fn = build_prefill_step(model)
            bshard = _ns(mesh, batch_specs(cfg, kw["batch"], rules, sizes))
            jf = jax.jit(fn, in_shardings=(pshard, bshard))
            lowered = jf.lower(params_shape, kw["batch"])
        else:  # decode
            cshard = _ns(mesh, cache_specs(cfg, kw["cache"], rules,
                                           shape.global_batch, sizes))
            dp = rules.dp_axis
            tshard = NamedSharding(
                mesh, P(dp, None) if shape.global_batch > 1 and dp is not None
                else P(None, None))
            fn = build_serve_step(model)
            jf = jax.jit(fn, in_shardings=(pshard, cshard, tshard, scalar),
                         out_shardings=(None, cshard), donate_argnums=(1,))
            lowered = jf.lower(params_shape, kw["cache"], kw["tokens"],
                               kw["index"])
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile(
            compiler_options={"xla_backend_optimization_level": 0})
        rec["compile_s"] = round(time.time() - t1, 2)

    rec.update(_collect(lowered, compiled))
    if collect_hlo:
        import gzip
        from repro.analysis.roofline import collective_bytes_from_hlo
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
        rec["collectives"] = collective_bytes_from_hlo(hlo)
        os.makedirs("results/hlo", exist_ok=True)
        tag_ = f"{arch}_{shape_name}_{'mp' if multi_pod else 'sp'}"
        if tag:
            tag_ += "_" + tag
        with gzip.open(f"results/hlo/{tag_}.hlo.gz", "wt") as f:
            f.write(hlo)
        rec["hlo_path"] = f"results/hlo/{tag_}.hlo.gz"
    rec["status"] = "ok"
    rec["params"] = cfg.param_count()
    rec["active_params"] = cfg.active_param_count()
    if verbose:
        mem = rec.get("memory", {})
        mesh_tag = ("1x1" if reduced else
                    "2x16x16" if multi_pod else "16x16")
        print(f"[{arch} × {shape_name} × {mesh_tag}] "
              f"lower {rec['lower_s']}s compile {rec['compile_s']}s "
              f"flops={rec.get('cost', {}).get('flops', float('nan')):.3e} "
              f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--scan", action="store_true",
                    help="layer-scan model (fast compile, body-once flops)")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cases already ok/skipped in --out")
    ap.add_argument("--reduced", action="store_true",
                    help="1-device smoke: reduced arch variants on a local "
                         "(1, 1) mesh, no placeholder devices")
    args = ap.parse_args()
    if args.reduced and (args.multi_pod or args.both_meshes):
        ap.error("--reduced runs on the local single mesh")
    if not args.reduced:
        # The production dry-run needs 512 placeholder devices.  jax locks
        # the device count at first backend init (not at import), so this
        # must precede the first device use below; set here rather than at
        # module import so merely importing this module never mutates the
        # process environment (tests import it, and a mutated XLA_FLAGS
        # would leak into any subprocess they spawn).
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=512 "
            + os.environ.get("XLA_FLAGS", ""))

    archs = list_archs() if args.arch is None or args.all else [args.arch]
    cheap_first = ["decode_32k", "long_500k", "prefill_32k", "train_4k"]
    shapes = cheap_first if args.shape is None or args.all else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    done = set()
    if args.skip_done and args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                r = json.loads(line)
                if r.get("status") in ("ok", "skipped"):
                    # reduced smoke records must not satisfy full-size
                    # cases (or vice versa) — the flag is part of the key
                    done.add((r["arch"], r["shape"], r["multi_pod"],
                              r.get("reduced", False)))

    records = []
    for shape in shapes:
        for arch in archs:
            for mp in meshes:
                if (arch, shape, mp, args.reduced) in done:
                    continue
                try:
                    rec = run_case(arch, shape, multi_pod=mp,
                                   use_scan=args.scan or mp,
                                   reduced=args.reduced)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    print(f"[{arch} × {shape}] ERROR {e!r}")
                records.append(rec)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")

    ok = sum(r["status"] == "ok" for r in records)
    sk = sum(r["status"] == "skipped" for r in records)
    er = sum(r["status"] == "error" for r in records)
    print(f"\ndry-run: {ok} ok, {sk} skipped (by design), {er} errors "
          f"of {len(records)} cases")
    if er:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
