"""Pallas TPU kernel: frontier gather — DMA row gather + neighbor reduce.

The batched traffic engine's hot loop (DESIGN: ISSUE 1) is "advance every
operation's frontier one level": for each vertex ``v``, reduce the frontier
rows of its in-neighbors. With the padded in-neighbor layout
(:class:`repro.graphs.structure.PaddedNeighbors`) that is a row gather
followed by an elementwise reduce over the neighbor slots.

Grid: ``(V / ROWS, C / c_tile)``. Each step owns a ``[ROWS, c_tile]``
output tile. The step's slice of the transposed neighbor table
``nbr.T [D, ROWS]`` is blocked into SMEM, the frontier ``x`` stays in HBM,
and the step starts one row DMA per (vertex, neighbor slot) into a
``[D, ROWS, c_tile]`` VMEM buffer — one row fetch per edge slot, the
roofline minimum for a frontier sweep — then reduces the slots on the
VPU. The weights arrive as an ordinary ``[ROWS, D]`` VMEM block. Nothing
grows with the graph except the grid: SMEM holds ``2 · D · ROWS`` ids
whatever ``V`` is, so paper-scale layouts (``V`` ≈ 786 k) fit the chip's
1 MiB of SMEM, which whole-table scalar prefetch did not.

``mode="sum"`` accumulates ``w · row`` (multiplicity propagation / BFS
expansion); ``mode="min"`` accumulates ``min(acc, row + w)`` (one min-plus
relaxation of the bucketed SSSP), with padded slots carrying ``w = +inf``.
Both reduce the slots in ascending order, like the XLA reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# Output rows per grid step (a multiple of 128, so the SMEM id block is
# lane-aligned). The VMEM gather buffer is D · ROWS · c_tile · 4 bytes.
ROWS = 128
# Widest neighbor layout the kernel takes: the buffer stays ≤ 4 MiB of
# VMEM at c_tile=128. The engine caps its layouts far below this.
MAX_SLOTS = 64


def _make_kernel(mode: str, d: int, rows: int):
    def kernel(nbr_ref, w_ref, x_hbm, o_ref, buf, sem):
        c_tile = o_ref.shape[1]
        col = pl.program_id(1) * c_tile

        def row_copy(i, j, src):
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(src, 1), pl.ds(col, c_tile)],
                buf.at[j, pl.ds(i, 1)],
                sem,
            )

        def start(i, carry):
            for j in range(d):
                row_copy(i, j, nbr_ref[j, i]).start()
            return carry

        def wait(i, carry):
            for j in range(d):
                row_copy(i, j, 0).wait()
            return carry

        jax.lax.fori_loop(0, rows, start, 0)
        jax.lax.fori_loop(0, rows, wait, 0)

        w = w_ref[...].astype(o_ref.dtype)
        if mode == "sum":
            acc = jnp.zeros(o_ref.shape, o_ref.dtype)
            for j in range(d):
                acc = acc + w[:, j:j + 1] * buf[j].astype(o_ref.dtype)
        else:
            acc = jnp.full(o_ref.shape, jnp.inf, o_ref.dtype)
            for j in range(d):
                acc = jnp.minimum(acc, buf[j].astype(o_ref.dtype) + w[:, j:j + 1])
        o_ref[...] = acc

    return kernel


def frontier_gather(
    x: jax.Array,        # [N, C] vertex-major frontier values
    nbr: jax.Array,      # [V, D] int32 in-neighbor ids (0 where padded)
    w: jax.Array,        # [V, D] float32: sum → w·mask; min → +inf where padded
    *,
    mode: str = "sum",
    c_tile: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Gather-reduce neighbor rows of ``x``; see module docstring.

    ``interpret=None`` resolves by backend at **call time** (outside the
    jitted inner function, via :func:`repro.kernels.resolve_interpret`):
    compiled on TPU, interpreter emulation elsewhere — so a TPU caller
    never silently runs interpreted, and the decision is not frozen into
    a trace made on the wrong backend.
    """
    return _frontier_gather_jit(
        x, nbr, w, mode=mode, c_tile=c_tile, interpret=resolve_interpret(interpret)
    )


@functools.partial(jax.jit, static_argnames=("mode", "c_tile", "interpret"))
def _frontier_gather_jit(
    x: jax.Array,
    nbr: jax.Array,
    w: jax.Array,
    *,
    mode: str,
    c_tile: int,
    interpret: bool,
) -> jax.Array:
    v, d = nbr.shape
    c = x.shape[1]
    if d > MAX_SLOTS:
        raise ValueError(f"{d} neighbor slots > {MAX_SLOTS}: cap the layout")
    c_pad = (-c) % c_tile
    if c_pad:
        x = jnp.pad(x, ((0, 0), (0, c_pad)))
    v_pad = (-v) % ROWS
    nbr_t = jnp.pad(nbr.astype(jnp.int32), ((0, v_pad), (0, 0))).T  # [D, V']
    w = jnp.pad(w, ((0, v_pad), (0, 0)))
    vt, ct = (v + v_pad) // ROWS, x.shape[1] // c_tile

    out = pl.pallas_call(
        _make_kernel(mode, d, ROWS),
        grid=(vt, ct),
        in_specs=[
            pl.BlockSpec((d, ROWS), lambda i, cc: (0, i), memory_space=pltpu.SMEM),
            pl.BlockSpec((ROWS, d), lambda i, cc: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((ROWS, c_tile), lambda i, cc: (i, cc)),
        out_shape=jax.ShapeDtypeStruct((v + v_pad, x.shape[1]), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, ROWS, c_tile), x.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(nbr_t, w, x)
    return out[:v, :c]
