"""End-to-end driver: a REAL hyper-parameter optimization study.

Trains a CIFAR-shaped ResNet (the paper's model family) with real JAX
training through the full Hippo stack — search plan, stage tree,
critical-path scheduler, checkpoint store, SHA tuner — and compares
stage-based against trial-based execution on actual wall-clock compute.

Sized for this CPU container (~2-4 minutes).  On a cluster the same code
runs with ``n_workers=40`` and the full ResNet56 (``ResNet(n=9)``).

    PYTHONPATH=src python examples/hpo_resnet.py
"""

import time

from repro.core import (Constant, MultiStep, SearchPlanDB, Study, merge_rate)
from repro.core.tuners import GridSearchSpace, SHATuner
from repro.data import DataPipeline, synthetic_cifar
from repro.models.resnet import ResNet
from repro.train.jax_trainer import JaxTrainer
from repro.utils.compile_cache import enable_compile_cache


def make_backend():
    data = synthetic_cifar(2048, seed=0)
    eval_data = synthetic_cifar(512, seed=1)
    return JaxTrainer(ResNet(n=1, width=16),
                      lambda: DataPipeline(data, batch_size=64, seed=3),
                      eval_data, default_optimizer="momentum")


def space():
    return GridSearchSpace(fns={
        "lr": [Constant(0.05),
               MultiStep(0.05, [40], values=[0.05, 0.005]),
               MultiStep(0.05, [40], values=[0.05, 0.02]),
               MultiStep(0.05, [60], values=[0.05, 0.005]),
               MultiStep(0.05, [60, 80], values=[0.05, 0.02, 0.002]),
               MultiStep(0.05, [80], values=[0.05, 0.01])],
        "bs": [Constant(64)]})


def main():
    enable_compile_cache()
    trials = space().trials(100)
    print(f"{len(trials)} trials × 100 steps, p = {merge_rate(trials):.2f}")

    results = {}
    for share, label in ((True, "stage"), (False, "trial")):
        db = SearchPlanDB()
        study = Study.create(db, "resnet8", "synthetic-cifar", ("lr", "bs"))
        tuner = SHATuner(space().trials(100), min_steps=25, max_steps=100,
                         eta=2)
        t0 = time.time()
        stats = study.run(tuner, make_backend(), n_workers=2)
        wall = time.time() - t0
        results[label] = (stats, tuner, wall)
        print(f"{label}-based: best val_acc {tuner.best_score:.4f}  "
              f"steps trained {stats.steps_run}  wall {wall:.1f}s")

    s, t = results["stage"][0], results["trial"][0]
    print(f"\nstage-based trained {t.steps_run / s.steps_run:.2f}x fewer "
          f"steps for the same search"
          f" (best acc stage {results['stage'][1].best_score:.4f} "
          f"vs trial {results['trial'][1].best_score:.4f})")


if __name__ == "__main__":
    main()
