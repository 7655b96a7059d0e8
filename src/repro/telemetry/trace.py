"""Names the program gives its work in a profiler trace, and the host
helper that times a phase on the same clock reads the trace shows.

Device side: ``jax.named_scope`` names, put around the model's layers and
the optimizer (``models/transformer.py``, ``models/model.py``,
``train/train_step.py``).  XLA keeps the scope in every operation's
``op_name`` metadata through scan, remat and transpose, so a trace's op
can be put down to its layer and to the phase it ran in: ``transpose(``
in the path marks the backward, ``rematted_computation`` the remat
recompute.  Work under none of them (embedding gather, norms, rotary,
the q/k/v/o projections, gradient accumulation) is "rest".

Host side: ``ElasticTrainer.train`` opens one :class:`Span` per phase of
a step, inside a ``StepTraceAnnotation`` named :data:`STEP`.  Each is a
``TraceAnnotation`` (free while no profiler runs) and two
``time.perf_counter`` reads; the step record, the telemetry bus and the
profiler's trace all read those same phases.

:func:`counters` gives the process's running totals of JAX compiles
(jaxpr traces and backend compiles, from ``jax.monitoring``) and of
Python garbage collections, with their seconds; a step's record holds the
difference across the step.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict

import jax

# device scopes
ATTENTION = "repro.attention"      # scores, mask, softmax, P.V
MLP = "repro.mlp"                  # the feed-forward block
HEAD = "repro.head"                # projection onto the vocabulary
LOSS = "repro.loss"                # cross-entropy and its normalisation
OPTIMIZER = "repro.optimizer"      # global norm, clip, AdamW
SCOPES = (ATTENTION, MLP, HEAD, LOSS, OPTIMIZER)

# host spans of ElasticTrainer.train
STEP = "repro.train.step"
DATA = "repro.train.data"
DISPATCH = "repro.train.dispatch"
SYNC = "repro.train.sync"
LOG = "repro.train.log"
CHECKPOINT = "repro.train.checkpoint"
RECONFIG = "repro.train.reconfig"

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class Span:
    """``with Span(name) as s:`` marks ``name`` in the profiler's trace and
    leaves the seconds it took in ``s.seconds``."""
    __slots__ = ("name", "seconds", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)


# Process-wide, as the jax.monitoring and gc listeners that feed them are.
# Reentrant: a collection can start inside a listener on the same thread.
_lock = threading.RLock()
_totals = {"compiles": 0, "compile_s": 0.0,
           "gc_collections": 0, "gc_s": 0.0}
_gc_start = [0.0]
_installed = False


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        with _lock:
            _totals["compiles"] += 1
            _totals["compile_s"] += duration_secs


def _on_gc(phase: str, _info) -> None:
    if phase == "start":
        _gc_start[0] = time.perf_counter()
        return
    with _lock:
        _totals["gc_collections"] += 1
        _totals["gc_s"] += time.perf_counter() - _gc_start[0]


def counters() -> Dict[str, float]:
    """The process's totals so far: ``compiles``, ``compile_s``,
    ``gc_collections``, ``gc_s``.  The listeners are registered on the
    first call, once per process."""
    global _installed
    with _lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            gc.callbacks.append(_on_gc)
            _installed = True
        return dict(_totals)


def since(before: Dict[str, float]) -> Dict[str, float]:
    """What the counters added since ``before = counters()``."""
    return {k: v - before[k] for k, v in counters().items()}
