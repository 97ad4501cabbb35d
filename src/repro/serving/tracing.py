"""Spans and program names for the serving engines, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: it records only while a
``jax.profiler`` trace is running and is a no-op (about a microsecond on the
host) otherwise, so the profiler is the switch and the spans share the
device trace's clock.  Every span the engines open is named ``serve:<phase>``
(docs/serving.md "Spans and counters" lists them); metadata such as a
request's ``rid`` rides as a trace statistic and leaves the name clean.

``jit_named`` compiles a function under its cache key, so the program's XLA
module reads ``jit_<name>`` (``jit_tgt_tree_p8``, ``jit_drf_step``) in a
device trace, where an anonymous ``functools.partial`` would read
``jit__unknown``.
"""
from __future__ import annotations

import functools

import jax


def span(name: str, **meta):
    """A host span named ``name``; ``meta`` values are trace statistics."""
    return jax.profiler.TraceAnnotation(name, **meta)


def step_span(n: int):
    """The span of one served step, marked as step ``n`` in the trace."""
    return jax.profiler.StepTraceAnnotation("serve:step", step_num=n)


def traced(name: str):
    """Decorator form of ``span``: each call of the method runs inside it."""
    return functools.partial(jax.profiler.annotate_function, name=name)


def jit_named(cache: dict, name: str, fn, donate_argnums=None):
    """``jax.jit(fn)`` kept in ``cache`` under ``name``, compiled as a
    program named ``name``.  ``donate_argnums`` marks arguments whose
    buffers XLA may update in place (pools and caches)."""
    if name not in cache:
        @functools.wraps(fn)
        def program(*args, **kwargs):
            return fn(*args, **kwargs)

        program.__name__ = program.__qualname__ = name
        kw = {} if donate_argnums is None else {"donate_argnums": donate_argnums}
        cache[name] = jax.jit(program, **kw)
    return cache[name]
