"""Real-training backend: Hippo stages driving a JAX model (the §5.2
``Trainer`` counterpart), with a fused data plane.

``JaxTrainer`` executes a whole stage as a handful of *chunk executables*:
each chunk is one compiled XLA call covering up to ``chunk_steps`` training
steps, consuming a prefetched data slab (``DataPipeline.next_batches``) and
stacked per-step hyper-parameter arrays (the ``setup(hp)`` hot-update of
Figure 9 becomes "hp values are traced inputs of the compiled chunk").
Compiled executables are cached on ``(opt_name, chunk_len, batch_shape,
hp structure)``; stage lengths are split into descending power-of-two
chunks so any length reuses O(log chunk_steps) executables.  Cache misses
compile ahead-of-time (``jit(...).lower().compile()``) with the time
recorded in ``compile_seconds``, which the dispatcher subtracts from its
wall-clock stage measurement — one-time compilation never distorts
seconds/step profiles (critical-path priorities) or the virtual clock.

The chunk body is backend-gated:

* **CPU** — a *statically unrolled* scan, semantically
  ``lax.scan(step, carry, (hp, slab, steps), unroll=chunk_len)`` with
  static slab indexing.  We deliberately avoid ``lax.scan`` itself here:
  its dynamic slicing of the data slab changes XLA:CPU's
  convolution-gradient codegen by 1-2 ulps, which would break the
  bit-exactness contract below.
* **GPU/TPU** — a real ``lax.scan`` over the slab (small HLO, fast
  compiles, better vectorization), with ``vectorize_groups`` defaulting on
  so sibling groups run under ``jax.vmap``, and the carry ``(params,
  opt)`` donated end-to-end between chunks.  Bit-exactness vs the CPU
  reference relaxes to ~1-2 ulps on these backends.

The gate keys on ``jax.default_backend()``; tests inject ``backend=`` (and
``donate=False``, since XLA:CPU cannot honor donation) to structure-test
the accelerator path on the CPU container.

Chain fusion: :meth:`run_chain` executes an entire scheduler-extracted
chain with the ``(params, opt)`` carry and the data pipeline held live
across every stage boundary — no checkpoint round-trip, no slab
re-prefetch, no restack between consecutive stages — while still
returning a boundary snapshot per stage for the dispatcher's write-behind
checkpointing.  :meth:`run_chains_batched` is the batched flavour: a group
of parallel sibling chains advances one stage level per compiled call over
a member-stacked carry that itself persists across boundaries.

Sibling-trial batching: :meth:`run_stages_batched` executes a whole group
of sibling stages — same ``[start, stop)``, same static hps and batch-size
schedule, divergent hp *values* — as ONE compiled call over member-stacked
carries, hp arrays and data slabs.  ``vectorize_groups`` follows the same
backend gate: off on CPU (members unroll statically, bit-exact per
member), on for accelerator backends (``jax.vmap`` over the member axis —
better vectorization, bit-exactness relaxed to ~1 ulp); pass it explicitly
to override the gate.

Everything a resumed trial needs is in the state pytree:

    {"params", "opt", "data" (pipeline position), "step"}

so stage-based execution is *lossless*: training a prefix once and forking
the checkpoint yields bit-identical parameters to training each trial
straight through, and the fused / batched paths are bit-identical to the
seed per-step loop (kept as :meth:`run_stage_stepwise`) — all asserted by
``tests/test_lossless.py``.

Batch-size sequences change the batch *shape* → new executable cache entry;
revisiting a size is free.

Kernel plane: ``use_kernel`` routes the hot math through the Pallas
kernels — the task's attention/SSD forward+backward
(:mod:`repro.kernels.ops`, set via the task's ``use_kernel`` attribute
when it has one) and the fused trial-stacked optimizer update
(:func:`repro.kernels.optim.fused_apply_update`) in every chunk body.
The default follows the backend gate: on for TPU (Mosaic codegen), off
otherwise; pass ``use_kernel=True`` explicitly to exercise the kernels
in interpret mode on CPU (correct but interpreter-slow — tests only).
All four execution paths (``run_stage``, ``run_stages_batched``,
``run_chain``, ``run_chains_batched``) share the same chunk bodies, so
they are uniformly kernel-aware; on the vmapped sibling-group path the
kernels' batching rules fold the member axis into the kernel grid (one
launch per group).  ``kernel_calls`` / ``kernel_fallbacks`` count kernel
call sites per compilation, not per launch (they move while a body is
traced; cumulative since this trainer's construction), for
``EngineStats``.  How often and how long the optimizer kernel ran is in
a device trace, under the ``hippo.opt_update`` scope.

Tree programs: the host-side tree work between executables is one
compiled dispatch each, never a loop of per-leaf eager ops — parameter
initialisation (``hippo_init``), an optimizer's zero slots
(``hippo_slots``), stacking a unit's member ``(params, opt)`` trees
(``hippo_stack``) and splitting the carry into member trees at each
boundary (``hippo_unstack``).  Slots, stacking and splitting are exact
data movement, bit for bit the eager ops'; the compiled init draws the
same random numbers, though XLA may fold a constant scale of a draw into
the sampler's own and round it an ulp or two apart (the ResNet's init
matches bit for bit).  JAX's jit cache keys the programs by member
count, tree, avals and optimizer name, so the warm-up's fresh states of
each width compile everything a study needs: members that arrive with
slots from a boundary, or as host numpy from a store tier, reuse the
same executables (a 1-device worker places its members on its device
before the stack, so its stack too sees one placement).
``tree_calls`` counts their dispatches beside ``exec_calls``.

Names in a profiler trace: the compiled callables are ``hippo_chunk``
(solo), ``hippo_group`` (sibling group), ``hippo_eval`` and the tree
programs above, so the device's modules are ``jit_hippo_chunk`` and so
on, and each host-side step of a call is a ``hippo.trainer.*`` span
(:mod:`repro.utils.spans`).

Mesh workers (distribution plane v2): :meth:`set_mesh` binds the trainer
to the dispatching worker's :class:`~repro.dist.meshes.WorkerMesh` before
each work unit.  A ``None`` mesh runs on the default device; a 1-device
mesh runs the same unsharded path on the device it owns — bit-identical
to thread-worker execution.  On a wider mesh the fused
carry lives **sharded at rest**: ``(params, opt)`` is placed with
:func:`repro.dist.sharding.generic_param_specs` (largest dividing dim →
``fsdp`` axis, largest remaining → ``tp``; PR 3's divisibility gate);
every chunk executable runs its body under ``shard_map`` on replicated
operands (the carry is all-gathered at entry; the TPU compiler cannot
partition a Pallas kernel itself), and the output re-scatters to the at-rest
placement *between* executables (``device_put``) — sharding is pure data
movement, so on CPU the sharded path stays bit-identical to the
unsharded one while the carry demonstrably lives distributed between
chunks.  Sibling groups stack members on a leading axis that is never
sharded (``n_lead=1``), so trial-batching (vmap) and sharding compose as
two orthogonal parallelism axes.  Boundary snapshots are gathered to one
device before they leave the trainer — checkpoints and eval stay
unsharded.  The live mesh key joins every executable cache key.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.trainer import (BatchIncompatible, StageContext,
                                 TrainerBackend)
from repro.core.values import desc_static, desc_values
from repro.data.pipeline import DataPipeline
from repro.dist.sharding import generic_param_specs
from repro.kernels import ops as kernel_ops
from repro.kernels.optim import fused_apply_update
from repro.train.checkpoint import stack_pytrees, unstack_pytree
from repro.train.optimizer import apply_update, init_opt_state
from repro.utils.spans import span

__all__ = ["JaxTrainer", "chunk_lengths"]


def chunk_lengths(n: int, max_chunk: int) -> List[int]:
    """Split ``n`` steps into descending power-of-two chunk lengths capped at
    ``max_chunk``, so every stage length reuses O(log max_chunk) compiled
    executables instead of compiling one per distinct length."""
    if max_chunk < 1:
        raise ValueError(f"max_chunk must be >= 1, got {max_chunk}")
    out: List[int] = []
    while n > 0:
        c = min(max_chunk, 1 << (n.bit_length() - 1))
        out.append(c)
        n -= c
    return out


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under a fixed name, which ``jax.jit`` gives the module it
    compiles (``jit_<name>``), so a device trace says what ran."""
    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    return named


# ------------------------------------------------------------ tree programs
# Module-level, so JAX's cache serves every trainer and study alike.

@partial(jax.jit, static_argnums=0)
@partial(_named, name="hippo_slots")
def _init_slots(opt_name: str, params: Any) -> Dict[str, Any]:
    """The optimizer's fresh slots for ``params`` (member-stacked or not)."""
    return init_opt_state(opt_name, params)


@jax.jit
@partial(_named, name="hippo_stack")
def _stack_members(members: List[Tuple[Any, Any]]) -> Tuple[Any, Any]:
    """``m`` member ``(params, opt)`` trees -> one member-stacked carry."""
    return stack_pytrees(members)


@partial(jax.jit, static_argnums=1)
@partial(_named, name="hippo_unstack")
def _unstack_members(carry: Tuple[Any, Any], m: int
                     ) -> List[Tuple[Any, Any]]:
    """A member-stacked carry -> its ``m`` member ``(params, opt)`` trees
    (not donated: the carry runs on into the next stage)."""
    return unstack_pytree(carry, m)


class JaxTrainer(TrainerBackend):
    """Stage executor over any task exposing ``init(rng)`` and
    ``loss(params, batch) -> (scalar, metrics)``."""

    def __init__(self, task, pipeline_factory: Callable[[], DataPipeline],
                 eval_batch: Dict[str, np.ndarray],
                 default_optimizer: str = "momentum", seed: int = 0,
                 objective_from: str = "acc", fused: bool = True,
                 chunk_steps: int = 8,
                 vectorize_groups: Optional[bool] = None,
                 backend: Optional[str] = None,
                 donate: Optional[bool] = None,
                 use_kernel: Optional[bool] = None):
        self.task = task
        self.pipeline_factory = pipeline_factory
        self.eval_batch = {k: jnp.asarray(v) for k, v in eval_batch.items()}
        self.default_optimizer = default_optimizer
        self.seed = seed
        self.objective_from = objective_from
        self.fused = fused
        self.chunk_steps = int(chunk_steps)
        if self.chunk_steps < 1:
            raise ValueError(f"chunk_steps must be >= 1, got {chunk_steps}")
        # backend gate (see module docstring).  ``backend`` is injectable so
        # the accelerator path is structure-testable on the CPU container.
        self.backend = backend or jax.default_backend()
        accel = self.backend != "cpu"
        self.use_scan = accel                   # lax.scan chunk bodies
        self.vectorize_groups = accel if vectorize_groups is None \
            else vectorize_groups
        # kernel plane (see module docstring): TPU-on by default, explicit
        # True runs interpret-mode kernels on CPU (tests), False = oracle
        self.use_kernel = (self.backend == "tpu") if use_kernel is None \
            else bool(use_kernel)
        if self.use_kernel and hasattr(task, "use_kernel"):
            task.use_kernel = True
        self._kernel_stats0 = kernel_ops.KERNEL_STATS.snapshot()
        self._step_fns: Dict[Tuple, Any] = {}   # stepwise per-step executables
        self._chunk_fns: Dict[Tuple, Any] = {}  # fused / batched executables
        # buffer donation frees the carry between chunks; XLA:CPU does not
        # implement it (and warns per call), so gate on the backend
        self._donate = accel if donate is None else donate
        self._eval_fn = jax.jit(_named(self.task.loss, "hippo_eval"))
        self._init_fn = jax.jit(_named(self.task.init, "hippo_init"))
        # Cumulative seconds spent AOT-compiling chunk executables.  The
        # dispatcher subtracts the per-stage delta from its measured wall so
        # one-time compilation never pollutes seconds/step profiles or the
        # virtual clock (a deployment amortizes compiles across the study).
        self.compile_seconds = 0.0
        self.exec_calls = 0       # compiled-executable dispatches issued
        self.tree_calls = 0       # tree-program dispatches issued
        # -------- mesh plane (distribution plane v2; see module docstring)
        self._wmesh = None                      # live WorkerMesh (>1 device)
        self._mesh = None                       # its jax.sharding.Mesh
        self._device = None                     # a 1-device mesh's device
        self._mesh_key: Optional[Tuple] = None  # joins executable cache keys
        self._meshes: Dict[Tuple, Any] = {}     # WorkerMesh.key -> jax Mesh
        self._mesh_ok: Dict[Tuple, bool] = {}   # mesh_compatible verdicts

    # ------------------------------------------------- kernel-plane counters
    @property
    def kernel_calls(self) -> int:
        """Kernel call sites per compilation since construction, not
        launches: the counter moves while a chunk body is traced.  The
        optimizer kernel's launches are the ``hippo.opt_update`` scope's
        operations in a device trace."""
        return kernel_ops.KERNEL_STATS.calls - self._kernel_stats0[0]

    @property
    def kernel_fallbacks(self) -> int:
        """Kernel→oracle fallbacks traced since construction."""
        return kernel_ops.KERNEL_STATS.fallbacks - self._kernel_stats0[1]

    # ------------------------------------------------------------ mesh plane
    def set_mesh(self, mesh) -> None:
        """Bind to the dispatching worker's mesh (None = thread worker).

        A thread worker runs on the default device.  A 1-device mesh runs
        unsharded on the device it owns: the carry is placed there and
        the executables, keyed by the mesh, are compiled for it, so a
        fleet of 1-chip workers spreads over the chips instead of sharing
        device 0.  The arithmetic is the thread worker's, bit for bit.
        The live ``jax.sharding.Mesh`` is built (and cached) once per
        distinct ``WorkerMesh.key``."""
        self._device = None
        if mesh is None:
            self._wmesh = self._mesh = self._mesh_key = None
            return
        key = mesh.key
        m = self._meshes.get(key)
        if m is None:
            m = mesh.jax_mesh()
            self._meshes[key] = m
        self._mesh_key = key
        if mesh.n_devices == 1:
            self._wmesh = self._mesh = None
            self._device = m.devices.flat[0]
        else:
            self._wmesh, self._mesh = mesh, m

    def mesh_compatible(self, mesh, ctxs) -> bool:
        """PR 3's divisibility gate as a placement gate: a >1-device mesh
        is only worth occupying when at least one parameter dimension
        actually shards under ``generic_param_specs`` — otherwise every
        leaf replicates and the extra devices buy nothing."""
        if mesh is None or mesh.n_devices == 1:
            return True
        ok = self._mesh_ok.get(mesh.key)
        if ok is None:
            shapes = jax.eval_shape(
                lambda: self.task.init(jax.random.PRNGKey(self.seed)))
            specs = generic_param_specs(shapes, mesh.rules, sizes=mesh.sizes)
            ok = any(any(ax is not None for ax in spec)
                     for spec in jax.tree.leaves(
                         specs, is_leaf=lambda x: isinstance(x, P)))
            self._mesh_ok[mesh.key] = ok
        return ok

    def clone_state(self, state):
        # jax array leaves are immutable — a fresh container tree is a
        # full-depth safe copy (the dispatcher's copy-on-fanout)
        return jax.tree.map(lambda x: x, state)

    def device_transfer(self, state, mesh):
        """Host-local handoff: re-home the device-resident leaves onto the
        consumer's first device inside a fresh container tree.  Declines
        (→ store fallback) when the mesh's devices are not visible to
        this process."""
        out = dict(state)
        if mesh is not None:
            try:
                dev = mesh.jax_mesh().devices.flat[0]
            except Exception:
                return None
            for k in ("params", "opt"):
                if out.get(k) is not None:
                    out[k] = jax.device_put(out[k], dev)
        return out

    def _carry_shardings(self, carry, n_lead: int):
        """NamedSharding tree for the at-rest carry placement (member-stack
        axis, when present, never shards)."""
        specs = generic_param_specs(carry, self._wmesh.rules,
                                    sizes=self._wmesh.sizes, n_lead=n_lead)
        return jax.tree.map(lambda s: NamedSharding(self._mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def _meshed_build(self, build):
        """Wrap a chunk-body builder for mesh execution.  The carry enters
        sharded at rest; the body runs under ``shard_map`` with every
        operand and result replicated, so the program gathers the carry
        at entry and each device runs the unsharded body on whole arrays
        — pure data movement, CPU-bitwise vs the unsharded build.
        ``shard_map`` is also what lets the body hold Pallas kernels: the
        TPU compiler refuses to partition a Mosaic call on its own.  The
        caller re-scatters the output to the at-rest placement with
        ``device_put`` between executables."""
        if self._mesh is None:
            return build
        mesh = self._mesh

        def wrapped_build():
            return jax.shard_map(build(), mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False)

        return wrapped_build

    @property
    def supports_batched_stages(self) -> bool:  # type: ignore[override]
        return self.fused

    @property
    def supports_chain_fusion(self) -> bool:  # type: ignore[override]
        return self.fused

    # ------------------------------------------------------------------ state
    def init_state(self) -> Dict[str, Any]:
        with span("hippo.trainer.init_state"):
            # the key is built on the host: a seed past 2**31 would not
            # survive as a traced int32
            params = self._init_fn(jax.random.PRNGKey(self.seed))
            self.tree_calls += 1
            pipe = self.pipeline_factory()
        return {
            "params": params,
            "opt": None,               # lazy: optimizer choice is a static hp
            "opt_name": None,
            "data": pipe.state(),
            "step": 0,
        }

    # -------------------------------------------------------------- stage prep
    def _stage_plan(self, ctx: StageContext):
        """Per-step value arrays, traced static hps, optimizer, hp names."""
        vals = desc_values(ctx.desc, ctx.node_start, ctx.start, ctx.stop)
        static = desc_static(ctx.desc)
        opt_name = static.get("optimizer", self.default_optimizer)
        static_hp = {k: float(v) for k, v in static.items()
                     if isinstance(v, (int, float)) and not k.startswith("_")}
        names = [k for k in vals if k != "bs"]
        return vals, static_hp, opt_name, names

    @staticmethod
    def _bs_runs(vals: Dict[str, List[float]], n: int
                 ) -> List[Tuple[int, int, Optional[int]]]:
        """Maximal runs ``[(i0, i1, bs)]`` of constant batch size; ``bs`` is
        None when the stage has no batch-size sequence (pipeline keeps its
        restored size)."""
        if "bs" not in vals:
            return [(0, n, None)]
        sizes = [int(round(v)) for v in vals["bs"]]
        runs, i0 = [], 0
        for i in range(1, n + 1):
            if i == n or sizes[i] != sizes[i0]:
                runs.append((i0, i, sizes[i0]))
                i0 = i
        return runs

    @staticmethod
    def _slab_sig(slab: Dict[str, np.ndarray]) -> Tuple:
        """Batch shape/dtype signature of a data slab (without the step axis)."""
        return tuple((k, tuple(v.shape[1:]), str(v.dtype))
                     for k, v in sorted(slab.items()))

    # ------------------------------------------------------------ executables
    def _slots(self, opt_name: str, params: Any) -> Dict[str, Any]:
        self.tree_calls += 1
        return _init_slots(opt_name, params)

    def _make_chunk_body(self, opt_name: str, n_steps: int):
        """The fused stage body: ``n_steps`` training steps over the
        slab/hp/step arrays.  Statically unrolled on CPU (bit-exact vs the
        per-step loop), a real ``lax.scan`` on accelerator backends — see
        the module docstring for the gate's rationale."""
        task = self.task
        update = fused_apply_update if self.use_kernel else apply_update

        if self.use_scan:
            def chunk(carry, static_hp, hp_xs, slab, steps):
                def body(c, xs):
                    hp_i, batch, step = xs
                    params, opt = c
                    hp = dict(static_hp)
                    hp.update(hp_i)
                    (loss, _), grads = jax.value_and_grad(
                        task.loss, has_aux=True)(params, batch)
                    with jax.named_scope("hippo.opt_update"):
                        params, opt = update(opt_name, params, grads, opt,
                                             hp, step)
                    return (params, opt), loss

                carry, losses = jax.lax.scan(body, carry,
                                             (hp_xs, slab, steps))
                return carry, losses[-1]

            chunk.uses_scan = True
            return chunk

        def chunk(carry, static_hp, hp_xs, slab, steps):
            params, opt = carry
            loss = jnp.float32(0)
            for i in range(n_steps):
                hp = dict(static_hp)
                hp.update({k: v[i] for k, v in hp_xs.items()})
                batch = {k: v[i] for k, v in slab.items()}
                (loss, _), grads = jax.value_and_grad(
                    task.loss, has_aux=True)(params, batch)
                with jax.named_scope("hippo.opt_update"):
                    params, opt = update(opt_name, params, grads, opt,
                                         hp, steps[i])
            return (params, opt), loss

        chunk.uses_scan = False
        return chunk

    def _call_executable(self, key: Tuple, build, donate: bool, args: Tuple):
        """Invoke the cached executable for ``key``, AOT-compiling on miss.
        The compiled callable is named for its kind (``key[0]``): a solo
        chunk is ``hippo_chunk``, a sibling group ``hippo_group``.

        Ahead-of-time ``lower().compile()`` (instead of first-call jit
        compilation) lets compilation time be accounted separately in
        ``compile_seconds`` — the dispatcher's wall-clock stage timing
        subtracts it, keeping profiles and virtual time execution-only."""
        # members and steps of the call: ("fused", opt, k, ...) or
        # ("group", opt, m, k, ...)
        m, k = (1, key[2]) if key[0] == "fused" else (key[2], key[3])
        exe = self._chunk_fns.get(key)
        if exe is None:
            with span("hippo.trainer.compile", m=m, k=k):
                t0 = time.perf_counter()
                name = "hippo_chunk" if key[0] == "fused" else "hippo_group"
                jitted = jax.jit(_named(build(), name),
                                 donate_argnums=(0,) if donate else ())
                exe = jitted.lower(*args).compile()
                self.compile_seconds += time.perf_counter() - t0
            self._chunk_fns[key] = exe
        self.exec_calls += 1
        with span("hippo.trainer.launch", m=m, k=k):
            return exe(*args)

    def _call_fused(self, opt_name: str, n_steps: int, slab_sig: Tuple,
                    hp_sig: Tuple, donate: bool, args: Tuple):
        key = ("fused", opt_name, n_steps, slab_sig, hp_sig, donate,
               self._mesh_key, self.use_scan)
        build = self._meshed_build(
            lambda: self._make_chunk_body(opt_name, n_steps))
        return self._call_executable(key, build, donate, args)

    def _call_group(self, opt_name: str, group: int, n_steps: int,
                    slab_sig: Tuple, hp_sig: Tuple, shared_slab: bool,
                    args: Tuple):
        """``shared_slab``: sibling groups forked from one checkpoint see
        the same data stream — the slab is gathered once and broadcast to
        every member inside the executable instead of stacked per member."""
        key = ("group", opt_name, group, n_steps, slab_sig, hp_sig,
               shared_slab, self._mesh_key, self.vectorize_groups,
               self.use_scan)

        def build():
            chunk = self._make_chunk_body(opt_name, n_steps)
            if self.vectorize_groups:
                return jax.vmap(chunk,
                                in_axes=(0, None, 0, None if shared_slab
                                         else 0, None))

            def grouped(carry, static_hp, hp_xs, slab, steps):
                outs, losses = [], []
                for g in range(group):
                    member = jax.tree.map(lambda x, g=g: x[g], carry)
                    hx = {k: v[g] for k, v in hp_xs.items()}
                    sl = slab if shared_slab else {k: v[g]
                                                   for k, v in slab.items()}
                    out, loss = chunk(member, static_hp, hx, sl, steps)
                    outs.append(out)
                    losses.append(loss)
                return stack_pytrees(outs), jnp.stack(losses)

            return grouped

        return self._call_executable(key, self._meshed_build(build),
                                     self._donate, args)

    # -------------------------------------------------------------- execute
    def run_stage(self, state: Dict[str, Any], ctx: StageContext
                  ) -> Dict[str, Any]:
        if not self.fused:
            return self.run_stage_stepwise(state, ctx)
        return self._run_fused([state], [ctx])[0]

    def run_stages_batched(self, states: Sequence[Dict[str, Any]],
                           ctxs: Sequence[StageContext]
                           ) -> List[Dict[str, Any]]:
        if not self.fused:
            return [self.run_stage_stepwise(s, c)
                    for s, c in zip(states, ctxs)]
        return self._run_fused(list(states), list(ctxs))

    def run_chain(self, state: Dict[str, Any],
                  ctxs: Sequence[StageContext]) -> List[Dict[str, Any]]:
        """Chain-fused execution: the carry stays on device across every
        stage boundary (one persistent pipeline, no restack, no host
        round-trip) and a boundary snapshot is returned per stage — bit-
        identical to running :meth:`run_stage` per stage on CPU."""
        if not self.fused:
            return super().run_chain(state, ctxs)
        return self._run_fused_chain([state], [list(ctxs)])[0]

    def run_chains_batched(self, states: Sequence[Dict[str, Any]],
                           chains: Sequence[Sequence[StageContext]]
                           ) -> List[List[Dict[str, Any]]]:
        """Batched multi-stage chains: every stage level of a sibling-chain
        group executes as one compiled call over member-stacked carries,
        and the stack itself persists across stage boundaries."""
        if not self.fused:
            return [self.run_chain(s, c) for s, c in zip(states, chains)]
        return self._run_fused_chain(list(states),
                                     [list(c) for c in chains])

    def _run_fused(self, states: List[Dict[str, Any]],
                   ctxs: List[StageContext]) -> List[Dict[str, Any]]:
        return [b[-1] for b in
                self._run_fused_chain(states, [[c] for c in ctxs])]

    def _run_fused_chain(self, states: List[Dict[str, Any]],
                         chains: List[List[StageContext]]
                         ) -> List[List[Dict[str, Any]]]:
        """Run ``group`` parallel chains (one per member) of equal depth,
        returning ``[member][stage]`` boundary states.

        The carry — ``(params, opt)``, member-stacked for groups — and the
        data pipelines persist across stage boundaries; each boundary only
        snapshots the carry (for groups: one ``hippo_unstack`` dispatch
        splits it into member trees) so the dispatcher can checkpoint it,
        then execution continues on device.  Before the first stage, the
        members' missing optimizer slots come from ``hippo_slots`` and a
        group's members are stacked by one ``hippo_stack`` dispatch.
        ``group == 1, depth == 1`` degenerates to the old fused
        single-stage path, ``group > 1, depth == 1`` to sibling batching."""
        group = len(states)
        depth = len(chains[0])
        for ch in chains[1:]:
            if len(ch) != depth:
                raise ValueError("batched chains must share their depth")
        with span("hippo.trainer.prepare", m=group, depth=depth):
            plans = [[self._stage_plan(c) for c in ch] for ch in chains]
            for ch in chains:   # stages of one chain must be contiguous
                step = ch[0].start
                for c in ch:
                    if c.start != step:
                        raise ValueError(
                            f"chain stages must be contiguous: stage starts "
                            f"at {c.start}, previous stopped at {step}")
                    step = c.stop

            opt_name = plans[0][0][2]
            params_l, opt_l = [], []
            for s, ch in zip(states, chains):
                assert s["step"] == ch[0].start, (s["step"], ch[0].start)
                params_l.append(s["params"])
                opt = s["opt"]
                if opt is None or s["opt_name"] != opt_name:
                    opt = self._slots(opt_name, s["params"])
                opt_l.append(opt)
            if self._device is not None:
                # members may arrive from the store (host) or another worker
                params_l, opt_l = jax.device_put((params_l, opt_l),
                                                 self._device)
            # siblings forked from one checkpoint share the data stream: one
            # pipeline (and one slab, broadcast in-executable) serves them all
            shared_data = group > 1 and all(
                tuple(s["data"]) == tuple(states[0]["data"])
                for s in states[1:])
            pipes = []
            for s in (states[:1] if shared_data else states):
                pipe = self.pipeline_factory()
                pipe.restore(s["data"])
                pipes.append(pipe)

            if group == 1:
                carry = (params_l[0], opt_l[0])
            else:
                carry = _stack_members(list(zip(params_l, opt_l)))
                self.tree_calls += 1
            n_lead = 0 if group == 1 else 1   # member-stack axis never shards
            carry_shd = None                  # at-rest NamedSharding tree
            if self._mesh is not None:
                carry_shd = self._carry_shardings(carry, n_lead)
                carry = jax.device_put(carry, carry_shd)
        boundaries: List[List[Dict[str, Any]]] = [[] for _ in range(group)]

        for j in range(depth):
            ctx0 = chains[0][j]
            n = ctx0.stop - ctx0.start
            vals0, static_hp0, stage_opt, names0 = plans[0][j]
            runs = self._bs_runs(vals0, n)
            for ch, pl in zip(chains[1:], plans[1:]):
                c = ch[j]
                vals, static_hp, opt_n, names = pl[j]
                if (c.start, c.stop) != (ctx0.start, ctx0.stop):
                    raise BatchIncompatible("batched stages must share [start, stop)")
                if opt_n != stage_opt or static_hp != static_hp0:
                    raise BatchIncompatible("batched stages must share static hps")
                if names != names0:
                    raise BatchIncompatible("batched stages must share hp names")
                if self._bs_runs(vals, n) != runs:
                    raise BatchIncompatible("batched stages must share the bs schedule")
            if j == 0 and runs and runs[0][2] is None and len(pipes) > 1:
                if len({p.batch_size for p in pipes}) > 1:
                    raise BatchIncompatible("batched stages must share the batch size")
            if stage_opt != opt_name:
                # optimizer switch at the boundary: fresh slots, exactly as
                # run_stage would re-init on the restored state
                carry = (carry[0], self._slots(stage_opt, carry[0]))
                opt_name = stage_opt
                if carry_shd is not None:    # fresh slots: back to at-rest
                    carry_shd = self._carry_shardings(carry, n_lead)
                    carry = jax.device_put(carry, carry_shd)
            hp_sig = (tuple(sorted(names0)), tuple(sorted(static_hp0)))

            # the previous boundary snapshot aliases the carry: the first
            # chunk after a snapshot (and the caller's state) is never
            # donated; later chunks within the stage own their carry
            first = True
            for i0, i1, bs in runs:
                if bs is not None:
                    for pipe in pipes:
                        pipe.set_batch_size(bs)
                w0 = i0
                for k_len in chunk_lengths(i1 - i0, self.chunk_steps):
                    w1 = w0 + k_len
                    with span("hippo.trainer.feed", k=k_len):
                        slabs = [pipe.next_batches(k_len) for pipe in pipes]
                        # host-side like the slabs: transferred to wherever
                        # the executable runs
                        steps = np.arange(ctx0.start + w0, ctx0.start + w1,
                                          dtype=np.int32)
                        if group == 1:
                            hp_xs = {k: np.asarray(vals0[k][w0:w1],
                                                   np.float32)
                                     for k in names0}
                        else:
                            hp_xs = {k: np.asarray([pl[j][0][k][w0:w1]
                                                    for pl in plans],
                                                   np.float32)
                                     for k in names0}
                            slab = (slabs[0] if shared_data else
                                    {k: np.stack([s[k] for s in slabs])
                                     for k in slabs[0]})
                    if group == 1:
                        carry, _ = self._call_fused(
                            opt_name, k_len, self._slab_sig(slabs[0]), hp_sig,
                            self._donate and not first,
                            (carry, static_hp0, hp_xs, slabs[0], steps))
                    else:
                        carry, _ = self._call_group(
                            opt_name, group, k_len, self._slab_sig(slabs[0]),
                            hp_sig, shared_data,
                            (carry, static_hp0, hp_xs, slab, steps))
                    if carry_shd is not None:
                        # re-scatter to the at-rest placement between
                        # executables (see _meshed_build)
                        carry = jax.device_put(carry, carry_shd)
                    first = False
                    w0 = w1

            # ---- boundary snapshot: per-member state the dispatcher can
            # checkpoint; the carry itself stays on device for stage j+1
            with span("hippo.trainer.snapshot", m=group):
                if group == 1:
                    members = [carry]
                else:
                    members = _unstack_members(carry, group)
                    self.tree_calls += 1
                if self._mesh is not None:
                    # snapshots leave the trainer unsharded: checkpoints, eval
                    # and cross-worker handoff all see single-device trees
                    dev = self._mesh.devices.flat[0]
                    members = [jax.device_put(mb, dev) for mb in members]
                datas = ([pipes[0].state()] * group if shared_data
                         else [p.state() for p in pipes])
                for m, (params, opt) in enumerate(members):
                    boundaries[m].append(
                        {"params": params, "opt": opt,
                         "opt_name": opt_name, "data": datas[m],
                         "step": ctx0.stop})
        return boundaries

    # ----------------------------------------------- seed per-step reference
    def _jitted_step(self, opt_name: str):
        key = ("step", opt_name)
        if key not in self._step_fns:
            update = fused_apply_update if self.use_kernel else apply_update

            def step_fn(params, opt, batch, hp, step):
                (loss, _), grads = jax.value_and_grad(
                    self.task.loss, has_aux=True)(params, batch)
                params, opt = update(opt_name, params, grads, opt,
                                     hp, step)
                return params, opt, loss
            self._step_fns[key] = jax.jit(step_fn)
        return self._step_fns[key]

    def run_stage_stepwise(self, state: Dict[str, Any], ctx: StageContext
                           ) -> Dict[str, Any]:
        """The seed data plane: one jitted dispatch per training step, batch
        re-materialized on host each iteration.  Kept as the bit-exactness
        reference for the fused/batched paths and as the benchmark baseline
        (``benchmarks/bench_dataplane.py``)."""
        assert state["step"] == ctx.start, (state["step"], ctx.start)
        vals, static_hp, opt_name, names = self._stage_plan(ctx)

        params = state["params"]
        opt = state["opt"]
        if opt is None or state["opt_name"] != opt_name:
            opt = init_opt_state(opt_name, params)

        pipe = self.pipeline_factory()
        pipe.restore(state["data"])
        step_fn = self._jitted_step(opt_name)

        for i, step in enumerate(range(ctx.start, ctx.stop)):
            if "bs" in vals:
                pipe.set_batch_size(int(round(vals["bs"][i])))
            batch = pipe.next_batch()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            hp = dict(static_hp)
            hp.update({k: vals[k][i] for k in names})
            params, opt, _ = step_fn(params, opt, batch, hp,
                                     jnp.int32(step))

        return {"params": params, "opt": opt, "opt_name": opt_name,
                "data": pipe.state(), "step": ctx.stop}

    # ------------------------------------------------------------- evaluate
    def evaluate(self, state: Dict[str, Any], ctx: StageContext
                 ) -> Dict[str, float]:
        with span("hippo.trainer.eval"):
            loss, metrics = self._eval_fn(state["params"], self.eval_batch)
        with span("hippo.trainer.eval_wait"):
            out = {"loss": float(loss)}
            out["val_acc"] = float(metrics.get(self.objective_from, -loss))
            for k, v in metrics.items():
                out[k] = float(v)
        return out

    def stage_seconds(self, ctx: StageContext) -> Optional[float]:
        return None  # wall-clock measured by the engine
