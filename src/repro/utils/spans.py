"""Named host spans at the program's layer boundaries.

A span is a ``jax.profiler.TraceAnnotation``: outside a profiler session
it does nothing, and inside one it lands in the profiler's own trace on
the same clock as the device's operations, so a reader can put each idle
gap of the device down to the layer the host was in.  Spans nest on the
host thread that opens them.  Their stats are ints or short strings
only (widths, step counts, a node id): a stat must cost no more than an
int to build, because the span is paid for on every call, traced or not.

``SPANS`` names every span the program opens.
"""

from __future__ import annotations

import jax

__all__ = ["span", "SPANS"]

SPANS = (
    "hippo.service.submit",     # StudyService.submit: admission
    "hippo.service.step",       # StudyService.step: one session step
    "hippo.service.close",      # StudyService.close: drain, flush, journal
    "hippo.engine.step",        # one event and the dispatcher call after it
    "hippo.tuner.on_result",    # a tuner's callback, promotions included
    "hippo.dispatch.round",     # one scheduling round
    "hippo.stagetree.build",    # Algorithm 1
    "hippo.scheduler.assign",   # choosing work units
    "hippo.dispatch.unit",      # one work unit (chain or group), end to end
    "hippo.ckpt.put",           # synchronous slice of a boundary save
    "hippo.ckpt.load",          # a resume load
    "hippo.ckpt.write",         # the write-behind thread's commit
    "hippo.trainer.init_state",  # parameter initialisation
    "hippo.trainer.prepare",    # plans, optimizer init, placement, stacking
    "hippo.trainer.feed",       # one chunk's slabs and hp arrays
    "hippo.trainer.launch",     # enqueue of one chunk executable
    "hippo.trainer.compile",    # AOT compile of a missing executable
    "hippo.trainer.snapshot",   # boundary states off the carry
    "hippo.trainer.eval",       # enqueue of an evaluation
    "hippo.trainer.eval_wait",  # the host blocked on the evaluation
)


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span called ``name`` (one of ``SPANS``) with ``stats``."""
    return jax.profiler.TraceAnnotation(name, **stats)
