"""The trainer's tree programs: the host-side tree work between chunk
executables (parameter init, optimizer slots, stacking member carries,
splitting them at boundaries) is one compiled dispatch each.

* Exactness: each program matches the eager helper it replaces
  (``task.init``, ``init_opt_state``, ``stack_pytrees``,
  ``unstack_pytree``) bit for bit, except where XLA folds a constant
  scale of a random draw into the sampler's own (see ``TASKS``).
* No compiles after the warm-up: a trainer warmed the way the chip
  benchmark's set-up warms it (``run_stages_batched`` from fresh states
  at every width 1-4) compiles nothing for members that arrive with
  slots from a boundary, as host numpy, or fresh at another width, and
  ``tree_calls`` counts the dispatches the unit should make.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core.trainer import StageContext
from repro.data import DataPipeline
from repro.dist.meshes import WorkerMesh
from repro.models.resnet import ResNet
from repro.train import jax_trainer
from repro.train.checkpoint import stack_pytrees, unstack_pytree
from repro.train.jax_trainer import JaxTrainer
from repro.train.optimizer import init_opt_state
from test_dataplane import TinyTask, tiny_backend, tiny_dataset

K = 8   # chunk steps: every stage below is one chunk

# a seed past 2**31, as the chip benchmark draws them
BIG_SEED = 2147483743


def _assert_bitwise(a, b, maxulp=0):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        if maxulp:
            np.testing.assert_array_max_ulp(x, y, maxulp)
        else:
            np.testing.assert_array_equal(x, y)


def _desc(lr, optimizer="momentum"):
    return {"hps": {"lr": {"kind": "const", "value": lr},
                    "momentum": {"kind": "const", "value": 0.9}},
            "static": {"optimizer": optimizer, "wd": 1e-4}}


def _ctxs(m, start, stop, tag="n"):
    return [StageContext(f"{tag}{i}", _desc(0.1 / (i + 1)), 0, start, stop,
                         f"pk{tag}{i}") for i in range(m)]


# ---------------------------------------------------------------------------
# exactness
# ---------------------------------------------------------------------------

# task, and the ulps by which its compiled init may differ from the eager
# one: none for the ResNet (its draws are scaled after a clamp), two for
# TinyTask, whose ``0.1 * normal`` XLA folds into the sampler's own
# ``sqrt(2) *``: one product of constants, rounded apart from the draws
TASKS = {"resnet": (lambda: ResNet(n=1, width=8), 0), "tiny": (TinyTask, 2)}


@pytest.mark.parametrize("m", [2, 3, 4])
@pytest.mark.parametrize("opt_name", ["momentum", "adam"])
@pytest.mark.parametrize("task_name", sorted(TASKS))
def test_tree_programs_match_eager_bitwise(task_name, opt_name, m):
    make_task, init_ulps = TASKS[task_name]
    task = make_task()
    backend = JaxTrainer(task, lambda: DataPipeline(tiny_dataset(), 8), {},
                         default_optimizer=opt_name, backend="cpu")
    params_l, opt_l = [], []
    for i in range(m):
        backend.seed = BIG_SEED + i
        params = backend.init_state()["params"]
        _assert_bitwise(params, task.init(jax.random.PRNGKey(BIG_SEED + i)),
                        init_ulps)
        slots = jax_trainer._init_slots(opt_name, params)
        _assert_bitwise(slots, init_opt_state(opt_name, params))
        params_l.append(params)
        # slots that differ per member and per slot, so stacking and
        # splitting them is not checked on zeros alone
        opt_l.append({k: jax.tree.map(lambda x, j=j: x * (j + 2.0) + i,
                                      params)
                      for j, k in enumerate(slots)})
    carry = jax_trainer._stack_members(list(zip(params_l, opt_l)))
    _assert_bitwise(carry, (stack_pytrees(params_l), stack_pytrees(opt_l)))
    members = jax_trainer._unstack_members(carry, m)
    _assert_bitwise(members, list(zip(unstack_pytree(carry[0], m),
                                      unstack_pytree(carry[1], m))))
    _assert_bitwise(members, list(zip(params_l, opt_l)))


# ---------------------------------------------------------------------------
# no compiles after the warm-up
# ---------------------------------------------------------------------------


class _Compiles:
    """Counts XLA compiles while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, *args, **kw):
        if self.on and event == self.EVENT:
            self.n += 1


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


def _warm(backend):
    """The chip benchmark's warm-up of the widths: one call per sibling-
    group width from fresh states (width 1: two chunks, the second
    donating its carry)."""
    for m in (1, 2, 3, 4):
        ctxs = _ctxs(m, 0, K * (2 if m == 1 else 1), tag=f"w{m}-")
        out = backend.run_stages_batched(
            [backend.init_state() for _ in range(m)], ctxs)
        jax.block_until_ready([o["params"] for o in out])


@pytest.fixture(scope="module", params=["thread", "one_device"])
def warmed(request):
    """A trainer warmed like the benchmark: a thread worker, or a worker
    on a 1-device mesh (whose inputs are committed to its device)."""
    backend = tiny_backend()
    if request.param == "one_device":
        backend.set_mesh(WorkerMesh.build([0]))
    _warm(backend)
    return backend


def _boundary_members(backend, m):
    """``m`` members that carry optimizer slots from a width-``m``
    boundary at step ``K``."""
    out = backend.run_stages_batched(
        [backend.init_state() for _ in range(m)], _ctxs(m, 0, K, tag="b"))
    jax.block_until_ready([o["params"] for o in out])
    return out


def _chain_depth2(backend, states):
    chains = [[c1, c2] for c1, c2 in zip(_ctxs(len(states), K, 2 * K),
                                         _ctxs(len(states), 2 * K, 3 * K))]
    return backend.run_chains_batched(states, chains)


SCENARIOS = {
    # width 3, depth 2, members with slots from a boundary:
    # 1 stack + 2 unstacks
    "boundary_slots": (lambda b: _boundary_members(b, 3), _chain_depth2, 3),
    # the same, arriving as host numpy (a store tier)
    "host_numpy": (lambda b: jax.device_get(_boundary_members(b, 3)),
                   _chain_depth2, 3),
    # width 4 from fresh members: 4 inits + 4 slot inits + 1 stack
    # + 1 unstack
    "fresh_width4": (lambda b: None,
                     lambda b, _: b.run_stages_batched(
                         [b.init_state() for _ in range(4)],
                         _ctxs(4, 0, K, tag="f")),
                     10),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_warm_trainer_compiles_nothing(compiles, warmed, scenario):
    make, run, want_calls = SCENARIOS[scenario]
    members = make(warmed)
    exes0, calls0 = len(warmed._chunk_fns), warmed.tree_calls
    compiles.n, compiles.on = 0, True
    try:
        jax.block_until_ready(run(warmed, members))
    finally:
        compiles.on = False
    assert compiles.n == 0
    assert len(warmed._chunk_fns) == exes0
    assert warmed.tree_calls - calls0 == want_calls
