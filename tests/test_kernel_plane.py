"""The kernel plane wired into the execution path: ``use_kernel=True``
training through :class:`JaxTrainer` must match the oracle path on every
execution tier — solo stages, chain-fused runs, and vmapped sibling
groups — with ``kernel_fallbacks == 0`` (the kernels really ran).

Tolerances
----------
The fused optimizer kernel computes the same f32 update as
``apply_update``, but the kernel call is a fusion boundary, so XLA
fuses the surrounding gradient arithmetic differently on the two paths
and the results differ by f32 rounding.  Measured on XLA:CPU: 5.8e-8 of
the largest parameter after 6 momentum steps, 1.2e-7 after an 8-step
chain, 3.4e-7 after one Adam step.  ``F32_RTOL`` = 2e-6 (about 16 f32
ulps of the largest parameter) holds these with margin and is some 2000x
below the ~4e-3 that rounding the update to bf16 would cost, so a kernel
computing in a lower precision fails it.

Adam is compared after ONE step.  Its update ``m̂ / (sqrt(v̂) + eps)``
is ``g / (|g| + eps)`` early on, so for the many near-zero gradients an
ulp of difference after step 1 becomes an O(lr) difference at step 2
(measured 1e-3): that is the optimizer's conditioning, not kernel error.

The vmapped sibling group stays bitwise: there the oracle is vmapped
too, and both paths fuse alike.
"""

import jax
import pytest

from repro.core import Constant, HpConfig, SearchPlanDB, Study
from repro.core.trainer import StageContext
from repro.core.trial import Trial
from repro.core.tuners import GridTuner
from repro.data import DataPipeline, synthetic_cifar
from repro.models.resnet import ResNet
from repro.train.jax_trainer import JaxTrainer

DATA = synthetic_cifar(128, seed=0)
EVAL = synthetic_cifar(64, seed=1)


def make_trainer(use_kernel, optimizer="momentum", **kw):
    return JaxTrainer(ResNet(n=1, width=8),
                      lambda: DataPipeline(DATA, batch_size=16, seed=3),
                      EVAL, default_optimizer=optimizer, backend="cpu",
                      use_kernel=use_kernel, **kw)


def desc(lr):
    return {"hps": {"bs": {"kind": "const", "value": 16.0},
                    "lr": {"kind": "const", "value": lr}}, "static": {}}


# see the module docstring: f32 rounding between two fusions of one update
F32_RTOL = 2e-6


def max_param_err(a, b):
    return max(float(jax.numpy.abs(x - y).max())
               for x, y in zip(jax.tree.leaves(a["params"]),
                               jax.tree.leaves(b["params"])))


def rel_param_err(a, b):
    """Largest parameter difference over the largest reference magnitude."""
    scale = max(float(jax.numpy.abs(y).max())
                for y in jax.tree.leaves(b["params"]))
    return max_param_err(a, b) / scale


def test_solo_stage_bitwise_with_momentum():
    ctx = StageContext("n0", desc(0.05), 0, 0, 6, "k0")
    kern = make_trainer(True)
    s_k = kern.run_stage(kern.init_state(), ctx)
    # counters are global deltas from each trainer's construction snapshot,
    # so build the oracle trainer after the kernel run
    orac = make_trainer(False)
    s_o = orac.run_stage(orac.init_state(), ctx)
    assert rel_param_err(s_k, s_o) < F32_RTOL
    assert kern.kernel_calls > 0
    assert kern.kernel_fallbacks == 0
    assert orac.kernel_calls == 0          # oracle path never hits kernels


def test_solo_stage_adam_short_horizon():
    """Adam: one-step kernel agreement (see module docstring — from step
    2 on, near-zero gradients turn an ulp into an O(lr) difference)."""
    ctx = StageContext("n0", desc(0.05), 0, 0, 1, "k0")
    kern = make_trainer(True, optimizer="adam")
    orac = make_trainer(False, optimizer="adam")
    s_k = kern.run_stage(kern.init_state(), ctx)
    s_o = orac.run_stage(orac.init_state(), ctx)
    assert rel_param_err(s_k, s_o) < F32_RTOL
    assert kern.kernel_fallbacks == 0


def test_chain_fused_bitwise_with_momentum():
    ctxs = [StageContext("n0", desc(0.05), 0, 0, 4, "k0"),
            StageContext("n1", desc(0.02), 0, 4, 8, "k0/n1")]
    kern = make_trainer(True)
    orac = make_trainer(False)
    b_k = kern.run_chain(kern.init_state(), ctxs)
    b_o = orac.run_chain(orac.init_state(), ctxs)
    assert rel_param_err(b_k[-1], b_o[-1]) < F32_RTOL
    assert kern.kernel_calls > 0
    assert kern.kernel_fallbacks == 0


def test_vmapped_sibling_group_bitwise_with_momentum():
    """Divergent per-member lrs ride the kernel grid as vector operands;
    each member still reproduces its oracle run exactly."""
    ctxs = [StageContext(f"m{i}", desc(0.05 * (1 + 0.1 * i)), 0, 0, 5,
                         f"k{i}") for i in range(3)]
    kern = make_trainer(True, vectorize_groups=True)
    orac = make_trainer(False, vectorize_groups=True)
    outs_k = kern.run_stages_batched([kern.init_state() for _ in ctxs], ctxs)
    outs_o = orac.run_stages_batched([orac.init_state() for _ in ctxs], ctxs)
    for s_k, s_o in zip(outs_k, outs_o):
        assert max_param_err(s_k, s_o) == 0.0
    assert kern.kernel_calls > 0
    assert kern.kernel_fallbacks == 0


def test_engine_stats_surface_kernel_counters():
    """A full engine run over a kernel-plane backend mirrors the trainer's
    counters into EngineStats — and matches the oracle engine within
    ``F32_RTOL``."""
    def run(backend):
        trial = Trial(HpConfig({"lr": Constant(0.05), "bs": Constant(16)}), 8)
        db = SearchPlanDB()
        study = Study.create(db, "resnet8", "synth", ("lr", "bs"))
        eng = study.engine(backend, n_workers=1)
        stats = eng.run([GridTuner([trial])])
        plan = db.get(study.key)
        leaf = plan.nodes[plan.trial_paths[trial.trial_id][-1]]
        return stats, eng.store.get(leaf.ckpts[8])["params"]

    kern = make_trainer(True)
    stats_k, params_k = run(kern)
    assert stats_k.kernel_calls > 0
    assert stats_k.kernel_fallbacks == 0

    orac = make_trainer(False)
    stats_o, params_o = run(orac)
    assert stats_o.kernel_calls == 0

    # same final params up to f32 rounding (see module docstring)
    assert rel_param_err({"params": params_k},
                         {"params": params_o}) < F32_RTOL


def test_forked_prefix_matches_straight_through_with_kernel():
    """The paper's invariant on the kernel path: a trial trained as a
    shared prefix, then forked into a vmapped sibling group, matches the
    same trial trained straight through solo, within ``F32_RTOL``."""
    lrs = (0.05, 0.02, 0.01)
    prefix = StageContext("n0", desc(0.05), 0, 0, 4, "k0")
    tails = [StageContext(f"m{i}", desc(lr), 4, 4, 8, f"k0/m{i}")
             for i, lr in enumerate(lrs)]
    kern = make_trainer(True, vectorize_groups=True)
    fork = kern.run_stage(kern.init_state(), prefix)
    forked = kern.run_stages_batched([fork] * len(tails), tails)
    for tail, s_f in zip(tails, forked):
        straight = kern.run_chain(kern.init_state(), [prefix, tail])[-1]
        assert rel_param_err(s_f, straight) < F32_RTOL
    assert kern.kernel_fallbacks == 0


def test_backend_gated_default():
    """use_kernel=None resolves from the backend: off on CPU (interpret
    mode is a test vehicle, not a perf win), on for TPU."""
    t = make_trainer(None)
    assert t.use_kernel is False
    assert jax.default_backend() == "cpu"
