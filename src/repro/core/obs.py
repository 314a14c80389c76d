"""Names of the stream path's phases, as a profiler sees them.

Two kinds of names, one prefix each, so that a reader of a profile can
find the program's own marks among XLA's op names and the caller's spans:

* ``scope(name)`` is ``jax.named_scope("ace/<name>")``.  It acts at trace
  time only: every op traced inside it carries ``ace/<name>`` in its
  ``op_name`` metadata, which a TPU profile reports per device op (the
  ``tf_op`` statistic), and the compiled program is otherwise unchanged.
  When scopes nest, the outermost one names the phase: the post-insert
  re-score is ``rescore`` even where it calls a score helper.
* ``span(name, **args)`` is a host span ``ace:<name>`` in the profiler's
  own trace, on the same clock as the device's ops.  Its keyword
  arguments come back as statistics of the event.  Inactive, it costs
  about a microsecond, so the stream path opens at most one per chunk and
  has no switch for it.

The phases: ``features``, ``hash``, ``score`` (the pre-insert gather),
``threshold``, ``insert`` (the scatter-add), ``rescore`` (the post-insert
counts, gathered or derived from the score's gather, and the Welford fold),
``summary`` (the per-chunk report) and, in a sliding window, ``rotate`` (the
ring's advance, once per epoch).
"""
from __future__ import annotations

import jax

SCOPE_PREFIX = "ace/"
SPAN_PREFIX = "ace:"


def scope(name: str):
    """Device scope ``ace/<name>`` around the ops traced inside it."""
    return jax.named_scope(SCOPE_PREFIX + name)


def span(name: str, **args):
    """Host span ``ace:<name>`` in the profiler's trace, with ``args`` as
    its statistics."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)
