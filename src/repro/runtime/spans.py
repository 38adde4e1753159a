"""Host spans at the layer boundaries of the coded round and the serve loop.

``span(name, **counters)`` is a ``jax.profiler.TraceAnnotation`` named
``spacdc.<name>``: it records only while a profiler session is active, on
the profiler's clock, beside the device operations, and costs under a
microsecond when none is.  Counters travel on the span as event stats, so
they must be known when it opens: host ints or short strings, never a
device value (reading one would wait for the chip).  ``SPANS`` lists every
name a call site may use; ``tests/test_spans.py`` holds the call sites to
it and to host counters.
"""

import jax

SPANS = {
    "round": "one coded round, any path",
    "round.plan": "worker pricing, straggler draw and plan_round",
    "round.dispatch": "enqueue of the round program",
    "round.wait": "block_until_ready on the round's product",
    "round.to_host": "copy of the product to the host (chunked, bytes)",
    "serve.admit": "one admission into a slot",
    "serve.step": "one loop step (bucket: padded rows, live: used rows)",
    "serve.inputs": "token, position and mask arrays of a step",
    "serve.plan": "the step's straggler plan and wire material",
    "serve.cache": "eager KV-cache slice, merge, zero or gather",
    "serve.dispatch": "enqueue of the step program (new_bucket)",
    "serve.wait": "block_until_ready on the step's outputs",
    "serve.to_host": "copy of next tokens or logits to the host",
    "serve.consume": "reading the step's tokens and evicting finishers",
    "trace": "tracing of a jitted round or step program (fn)",
}


def span(name: str, **counters):
    """The span ``spacdc.<name>`` carrying ``counters``."""
    return jax.profiler.TraceAnnotation("spacdc." + name, **counters)
