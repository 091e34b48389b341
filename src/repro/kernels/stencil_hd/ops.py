"""jit'd public wrapper for the Jacobi stencil kernel."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import jacobi_pallas, jacobi_pallas_into
from .ref import jacobi_ref


def resolve_impl(impl: str) -> str:
    """``"auto"`` is the Pallas kernel on TPU and the jnp oracle
    elsewhere; any other ``impl`` names itself."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


@partial(jax.jit, static_argnames=("rows", "cols", "impl", "interpret"))
def jacobi_step(x, *, into=None, rows=None, cols=None, impl: str = "auto",
                interpret: bool = True):
    """One Jacobi sweep of ``x``.

    Without ``into``: a new array, first/last rows and columns passed
    through.  With ``into`` (Pallas only): the sweep over the static
    interior region ``rows`` x ``cols`` (``(start, stop)`` pairs)
    written into ``into``, which keeps its own values everywhere else;
    ``into``'s buffer is updated in place when the caller donates it."""
    impl = resolve_impl(impl)
    interpret = interpret and jax.default_backend() != "tpu"
    if into is not None:
        if impl != "pallas":
            raise ValueError("jacobi_step: into= is the Pallas "
                             "stencil's in-place form")
        return jacobi_pallas_into(x, into, rows=rows, cols=cols,
                                  interpret=interpret)
    if impl == "pallas":
        return jacobi_pallas(x, interpret=interpret)
    return jacobi_ref(x)
