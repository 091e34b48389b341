"""Plain reference of the ping-pong Jacobi sweep.

One sweep replaces every interior element of an (M, N) grid by the
mean of its four neighbours, ``((left + right) + above) + below`` times
0.25, and keeps the first and last row and column.  Each sweep makes a
new grid from the old one in one elementwise pass.  Two arrays that
alternate as source and destination, both starting from the same
grid, give after k sweeps what k sweeps of one grid give, so the
reference iterates one grid.

On one device it is plain ``jax.numpy`` slicing in a ``fori_loop``.
Over a mesh the grid is split by rows, and each sweep swaps one
boundary row with each neighbour (``ppermute``) before the same
arithmetic: the reference's own exchange, not the program's.

``dtype`` is the precision the sweeps run in: float32 as the
configuration states, bfloat16 for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _sweep(x, above, below, top, bottom):
    """x (R, N): rows of the grid; above/below (1, N): the rows just
    outside them; top/bottom: whether x's first/last row is the grid's
    boundary row.  Returns x after one sweep.

    The body rows are one elementwise pass over x padded with zeros
    (the zeros reach only rows 0 and R-1 and columns 0 and N-1, which
    are redone or kept); rows 0 and R-1 are then redone with the rows
    outside, in the same order of additions."""
    R, N = x.shape
    q = jnp.asarray(0.25, x.dtype)
    p = jnp.pad(x, 1)
    mid = ((p[1:-1, :-2] + p[1:-1, 2:]) + p[:-2, 1:-1]) + p[2:, 1:-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (R, N), 1)
    new = jnp.where((col == 0) | (col == N - 1), x, mid * q)

    def edge_row(row, up, dn, keep):
        m = ((row[:-2] + row[2:]) + up[1:-1]) + dn[1:-1]
        return jnp.where(keep, row[1:-1], m * q)

    new = new.at[0, 1:-1].set(edge_row(x[0], above[0], x[1], top))
    return new.at[-1, 1:-1].set(edge_row(x[-1], x[-2], below[0], bottom))


def _one_device(x, n):
    z = jnp.zeros((1, x.shape[1]), x.dtype)
    return jax.lax.fori_loop(
        0, n, lambda _, x: _sweep(x, z, z, True, True), x)


def _sharded(x, n, *, p: int):
    """Per-shard body of the row-split sweep (inside shard_map)."""
    me = jax.lax.axis_index("r")
    down_perm = [(i, i + 1) for i in range(p - 1)]
    up_perm = [(i + 1, i) for i in range(p - 1)]

    def body(_, x):
        above = jax.lax.ppermute(x[-1:], "r", down_perm)
        below = jax.lax.ppermute(x[:1], "r", up_perm)
        return _sweep(x, above, below, me == 0, me == p - 1)
    return jax.lax.fori_loop(0, n, body, x)


def sweeps_fn(devices):
    """A jitted ``f(x, n) -> x after n sweeps`` on ``devices`` (one, or
    a row mesh), with ``x`` placed as ``place`` puts it.  Returns
    ``(f, place)``."""
    if len(devices) == 1:
        place = functools.partial(jax.device_put, device=devices[0])
        return jax.jit(_one_device, donate_argnums=0), place
    mesh = jax.sharding.Mesh(list(devices), ("r",))
    rows = NamedSharding(mesh, P("r", None))
    f = jax.shard_map(functools.partial(_sharded, p=len(devices)),
                      mesh=mesh, in_specs=(P("r", None), P()),
                      out_specs=P("r", None), check_vma=False)
    return (jax.jit(f, donate_argnums=0),
            functools.partial(jax.device_put, device=rows))
