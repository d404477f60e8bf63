"""MG008 fixture: per-call jit, traced branch, unhashable static.

Never imported; scanned by tests/test_mglint.py. The jitted bodies
deliberately contain no while_loop so MG010 stays silent here.
"""
from functools import partial

import jax
import jax.numpy as jnp

_CACHE = {}


def _kernel(x):
    return x * 2.0


def rebuild_every_call(x):
    fn = jax.jit(_kernel)           # MG008 jit-per-call (line 19)
    return fn(x)


def cached_builder_is_silent(x, key):
    fn = _CACHE.get(key)
    if fn is None:
        fn = _CACHE[key] = jax.jit(_kernel)   # cached: silent
    return fn(x)


def suppressed_rebuild(x):
    fn = jax.jit(_kernel)  # mglint: disable=MG008 — fixture: deliberate
    return fn(x)


@jax.jit
def branchy(x, t):
    if t > 0:                       # MG008 traced-branch (line 37)
        return x * t
    return x


@jax.jit
def structural_branches_are_silent(x, t):
    if t is None:                   # pytree structure: silent
        return x
    if x.ndim > 1:                  # shape attribute: silent
        return x.sum(axis=0)
    return x + t


@partial(jax.jit, static_argnames=("opts",))
def unhashable(x, opts=[1, 2]):     # MG008 unhashable-static (line 52)
    return x * len(opts)


@partial(jax.jit, static_argnames=("k",))
def hashable_static_is_silent(x, k=3):
    return x * k


# --- jit-per-object: the shape ops/spmv_mxu.make_semiring_kernel had ----

def make_kernel(plan, delta=None):
    scale = plan.scale

    @jax.jit
    def run_impl(blob):             # MG008 jit-per-object (line 67)
        return blob * scale

    return run_impl


def kernel_of_snapshot(graph):
    """Memoised, but on the snapshot: the next write brings a new one."""
    cached = getattr(graph, "_state", None)
    if cached is None:
        cached = make_kernel(graph.plan, delta=graph.delta)
        object.__setattr__(graph, "_state", cached)
    return cached


_PROGRAMS = {}


def _build_program(sig):
    @jax.jit
    def run_impl(blob):             # keyed by a table: silent
        return blob * sig[0]

    return run_impl


def _program(sig):
    fn = _PROGRAMS.get(sig)
    if fn is None:
        fn = _PROGRAMS[sig] = _build_program(sig)
    return fn


def kernel_of_shapes(graph):
    """The repair: the program by what is static, the arrays as
    arguments; the snapshot keeps only its data."""
    cached = getattr(graph, "_state", None)
    if cached is None:
        cached = (_program((graph.plan.scale,)), graph.blob)
        object.__setattr__(graph, "_state", cached)
    return cached
