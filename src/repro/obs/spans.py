"""Program spans: named host spans on the JAX profiler's clock.

Each phase of the serving engine's step and of one RNN request is wrapped
in a :func:`span`.  With no profiler running a span costs about a
microsecond; under ``jax.profiler`` it lands in the trace's host plane,
on the same clock as the device's ops, so a reader of the trace can say
which phase the device waited on.  The names are plain, fixed and listed
in :data:`SPANS`:

* ``engine.step`` — all of ``ServingEngine.step``;
* ``engine.schedule`` — preempt and admit;
* ``engine.prefill`` — one same-bucket prefill group (inside
  ``engine.schedule``);
* ``engine.launch`` — chunk coverage, the merge of pending first tokens
  and the decode-chunk dispatch;
* ``engine.readback`` — the chunk's blocking ``device_get``;
* ``engine.bookkeep`` — everything after the readback;
* ``rnn.plan`` — the lookup of the fused RNN kernel's tile, chosen once
  per configuration;
* ``rnn.operands`` — the host-side pick of the request program and its
  arguments (the zero states are built inside the program);
* ``rnn.launch`` — the one dispatch of the request program.
"""

from __future__ import annotations

import jax

SPANS = ("engine.step", "engine.schedule", "engine.prefill",
         "engine.launch", "engine.readback", "engine.bookkeep",
         "rnn.plan", "rnn.operands", "rnn.launch")


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of :data:`SPANS`) in the profiler's
    trace; use as a context manager."""
    return jax.profiler.TraceAnnotation(name)
