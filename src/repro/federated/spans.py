"""Names of the program's profiler spans and device stage scopes.

Host spans are ``jax.profiler.TraceAnnotation``s: they land in a
``jax.profiler.trace`` on the device trace's clock and cost next to
nothing when no profiler runs. Device stages are ``jax.named_scope``s:
they only add ``op_name`` metadata to the ops traced inside them, so they
change no number and no compiled schedule. Every name starts with
``fl.``; ``bench/stages.py`` reads them back from a trace.

Host spans (``simulation.run``, ``ucfl``):

* ``fl.init`` — ``strategy.init``; inside it ``fl.init.gradients`` (the
  special round's per-client gradient pass with σ²), ``fl.init.gram``
  (the Δ Gram program), ``fl.init.weights`` (Δ and Eq. 9 W),
  ``fl.init.kmeans`` (§IV-B clustering, when set) and ``fl.init.slab``
  (the stacked state buffers). For ucfl the gradient, Gram, weights
  and slab spans each wrap one compiled launch;
* ``fl.warmup`` — the warm-up round on a copy, its sync and finite check;
* ``fl.round`` — one rounds-loop iteration, a profiler step; inside it
  ``fl.cohort`` (key split and cohort draw), ``fl.dispatch``
  (``strategy.round``), ``fl.check_finite`` and ``fl.evaluate`` (the
  pass ``History.eval_s`` times).

Device stages: in the cohort round programs ``fl.gather``,
``fl.local_sgd``, ``fl.wire``, ``fl.upload``, ``fl.w_refresh``,
``fl.mix``; in ucfl's init programs ``fl.init.gradients``,
``fl.init.gram`` and ``fl.init.weights``, the names of the host spans
that launch them.
"""
from __future__ import annotations

import jax

INIT = "fl.init"
INIT_GRADIENTS = "fl.init.gradients"
INIT_GRAM = "fl.init.gram"
INIT_WEIGHTS = "fl.init.weights"
INIT_KMEANS = "fl.init.kmeans"
INIT_SLAB = "fl.init.slab"
WARMUP = "fl.warmup"
ROUND = "fl.round"
COHORT = "fl.cohort"
DISPATCH = "fl.dispatch"
CHECK_FINITE = "fl.check_finite"
EVALUATE = "fl.evaluate"

GATHER = "fl.gather"
LOCAL_SGD = "fl.local_sgd"
WIRE = "fl.wire"
UPLOAD = "fl.upload"
W_REFRESH = "fl.w_refresh"
MIX = "fl.mix"


def span(name: str):
    """A host span ``name`` in the profiler's trace (inert without one)."""
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """A device stage ``name``: the ``op_name`` prefix of the ops traced
    inside it."""
    return jax.named_scope(name)
