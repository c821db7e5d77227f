"""jit'd public wrappers for the frontier gather kernel.

``make_frontier_gather(pn, mode=...)`` closes over a host-side
:class:`repro.graphs.structure.PaddedNeighbors` and returns a jitted
``x [N, C] -> reduced [N, C]`` callable: the Pallas kernel on TPU (interpret
mode available for validation on CPU), or the pure-jnp reference.

Capped layouts are fully supported: the rectangular slots go through the
gather kernel, and the few over-cap (COO spill) edges are combined in a
scatter epilogue — ``scatter-add`` for ``mode="sum"``, ``scatter-min`` for
``mode="min"``. This is exactly the batched traffic engine's GIS layout, so
:func:`frontier_relax` below *is* the engine's SSSP relaxation hot loop
(:mod:`repro.core.traffic_batched` calls it every round): the unrolled-slot
XLA form by default, the Pallas kernel when asked for, bit-identical
results either way (min and float32 add are exact and slot-order
independent).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.structure import PaddedNeighbors
from repro.kernels import resolve_interpret
from repro.kernels.frontier.kernel import frontier_gather
from repro.kernels.frontier.ref import frontier_gather_ref

_INF = jnp.float32(jnp.inf)


def _spill_epilogue(out, x, spill_s, spill_r, spill_w, mode: str):
    """Fold the COO spill tail into a gathered result (traceable).

    Padded spill entries carry ``w = +inf`` (min identity) for ``min`` and
    must carry ``w = 0`` (sum identity) for ``sum``.
    """
    if spill_s.shape[0] == 0:
        return out
    rows = x[spill_s]  # [S, C]
    if mode == "sum":
        return out.at[spill_r].add(spill_w[:, None] * rows)
    if mode == "min":
        return out.at[spill_r].min(rows + spill_w[:, None])
    raise ValueError(f"unknown mode {mode!r}")


def frontier_relax(
    x: jax.Array,          # [N, C] vertex-major frontier values
    nbr: jax.Array,        # [V, D] int32 in-neighbor ids (0 where padded)
    w_inf: jax.Array,      # [V, D] float32 weights, +inf where padded
    spill_s: jax.Array,    # [S] int32 senders of over-cap edges
    spill_r: jax.Array,    # [S] int32 receivers of over-cap edges
    spill_w: jax.Array,    # [S] float32 weights, +inf where padded
    *,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One min-plus relaxation over a capped gather layout + spill tail.

    ``out[v, c] = min( min_j x[nbr[v,j], c] + w_inf[v,j],
                       min over spill edges u→v of x[u, c] + w )``

    Traceable (safe to call inside an enclosing jit — pass an explicit
    ``interpret`` resolved at closure-build time, as the traffic engine
    does). ``use_kernel=True`` routes the rectangular slots through the
    Pallas kernel; otherwise an unrolled-slot gather (one fused
    gather+min per slot, the faster form on CPU and on a TPU v5e).
    """
    if use_kernel:
        acc = frontier_gather(x, nbr, w_inf, mode="min", interpret=interpret)
    else:
        c = x.shape[1]
        acc = jnp.full((nbr.shape[0], c), _INF, dtype=x.dtype)
        for j in range(nbr.shape[1]):
            acc = jnp.minimum(acc, x[nbr[:, j]] + w_inf[:, j][:, None])
    return _spill_epilogue(acc, x, spill_s, spill_r, spill_w, mode="min")


def make_frontier_gather(
    pn: PaddedNeighbors,
    mode: str = "sum",
    use_kernel: bool = False,
) -> Callable[[jax.Array], jax.Array]:
    """Return a jitted ``x [N, C] -> out [N, C]`` frontier reduce.

    Capped layouts (``pn.n_spill > 0``) are handled by a scatter epilogue
    over the spill tail; the rectangular slots still stream through the
    gather kernel / reference.
    """
    nbr = jnp.asarray(pn.nbr, dtype=jnp.int32)
    spill_s = jnp.asarray(pn.spill_s, dtype=jnp.int32)
    spill_r = jnp.asarray(pn.spill_r, dtype=jnp.int32)
    if mode == "sum":
        w = jnp.asarray(pn.w * pn.mask)
        spill_w = jnp.asarray(pn.spill_w)
    elif mode == "min":
        w = jnp.asarray(np.where(pn.mask > 0, pn.w, np.float32(np.inf)))
        spill_w = jnp.asarray(pn.spill_w)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    if use_kernel:
        interpret = resolve_interpret()

        @jax.jit
        def gather(x: jax.Array) -> jax.Array:
            out = frontier_gather(x, nbr, w, mode=mode, interpret=interpret)
            return _spill_epilogue(out, x, spill_s, spill_r, spill_w, mode)

    else:
        maskj = jnp.asarray(pn.mask)
        wj = jnp.asarray(pn.w)

        @jax.jit
        def gather(x: jax.Array) -> jax.Array:
            out = frontier_gather_ref(x, nbr, wj, maskj, mode=mode)
            return _spill_epilogue(out, x, spill_s, spill_r, spill_w, mode)

    return gather
