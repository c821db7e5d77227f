"""Plain DiDiC repair iteration in NumPy (arXiv:1301.5121 §4.1.3).

One maintenance iteration of disturbed diffusion over ``k`` load systems,
with the synchronous adaptations the program documents (fresh secondary
seeds, column-common rescale, assignment smoothing at full width,
ScaleBalance, deterministic commit):

    l   = 100·onehot(parts) + 0.01,  b = 10 on members, 1 elsewhere
    ψ times:  ρ times  l ← l − deg_c⊙(l/b) + A_c(l/b)
              w ← w + l − deg_c⊙w + A_c w
    w   ← w / mean(w)
    x   = smoothing_steps × (x ← x/2 + (A_c x)/2 / deg_c),  from x = w
    β   fitted by ``balance_iters`` rounds of β ← clip(β·(N/k / |argmax(xβ)|)^e)
    parts ← argmax(x·β)

``A_c`` is the symmetrized adjacency with Metropolis coefficients
``c_e = wt(e) / (1 + max(D_u, D_v))`` (``D`` the weighted degree) and
``deg_c`` its row sums. The carried state is ``(w, β)``; a repair from no
state starts at ``w = 100·onehot(parts)``, ``β = 1``.

``precision="bfloat16"`` is the control: every stored array rounded to
bfloat16 (sums accumulate in float32, as a bfloat16 path would).
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np
import scipy.sparse

INIT_LOAD = 100.0
BENEFIT = 10.0


def to_bf16(a: np.ndarray) -> np.ndarray:
    """float32 array rounded to bfloat16 values (nearest, ties to even)."""
    b = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return b.view(np.float32)


@dataclasses.dataclass(frozen=True)
class DidicParams:
    k: int
    primary_steps: int = 11
    secondary_steps: int = 9
    smoothing_steps: int = 64
    balance_iters: int = 8
    balance_exp: float = 0.25


class DidicRepair:
    """One-iteration DiDiC repair over a symmetrized edge list."""

    def __init__(self, und_s, und_r, und_w, n_nodes: int, params: DidicParams,
                 precision: str = "float32"):
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.n, self.p = int(n_nodes), params
        self.q = to_bf16 if precision == "bfloat16" else (lambda a: a)
        s = np.asarray(und_s, dtype=np.int64)
        r = np.asarray(und_r, dtype=np.int64)
        wt = np.asarray(und_w, dtype=np.float64)
        deg = np.bincount(s, weights=wt, minlength=self.n)
        ce = (wt / (1.0 + np.maximum(deg[s], deg[r]))).astype(np.float32)
        self.degc = np.bincount(s, weights=ce, minlength=self.n).astype(np.float32)[None, :]
        self.safe_deg = np.maximum(self.degc, np.float32(1e-6))
        self.a = scipy.sparse.csr_matrix((ce, (s, r)), shape=(self.n, self.n))
        self.pool = None
        self.spmm_count = 0

    def spmm(self, x: np.ndarray) -> np.ndarray:
        """A_c @ x for loads stored one system per row ([k, N]), float32,
        one thread per load system."""
        self.spmm_count += 1
        out = np.empty_like(x)

        def system(j):
            out[j] = self.a @ x[j]

        list(self.pool.map(system, range(x.shape[0])))
        return self.q(out)

    def iterate(self, parts: np.ndarray, state: Optional[Tuple[np.ndarray, np.ndarray]]):
        """Repair ``parts``; returns (new parts, new state (w [N, k], β))."""
        with ThreadPoolExecutor(self.p.k) as self.pool:
            return self._iterate(parts, state)

    def _iterate(self, parts, state):
        p, q, f32 = self.p, self.q, np.float32
        parts = np.asarray(parts, dtype=np.int64)
        onehot = (np.arange(p.k)[:, None] == parts[None, :]).astype(f32)
        if state is None:
            w, beta = f32(INIT_LOAD) * onehot, np.ones(p.k, dtype=f32)
        else:
            w, beta = np.ascontiguousarray(state[0].T, dtype=f32), state[1]
        w = q(w)
        l = q(f32(INIT_LOAD) * onehot + f32(0.01))
        benefit = np.where(onehot > 0, f32(BENEFIT), f32(1.0))
        dc = self.degc
        lb, t = np.empty_like(l), np.empty_like(l)
        for _ in range(p.primary_steps):
            for _ in range(p.secondary_steps):
                lb = q(np.divide(l, benefit, out=lb))
                l = q(np.subtract(l, q(np.multiply(dc, lb, out=t)), out=l))
                l = q(np.add(l, self.spmm(lb), out=l))
            aw = self.spmm(w)
            t = q(np.multiply(dc, w, out=t))
            w = q(np.add(w, l, out=w))
            w = q(np.subtract(w, t, out=w))
            w = q(np.add(w, aw, out=w))
        w = q(w / f32(max(float(w.mean(dtype=np.float64)), 1e-6)))
        x = w.copy()
        for _ in range(p.smoothing_steps):
            sm = q(np.divide(q(np.multiply(f32(0.5), self.spmm(x), out=t)), self.safe_deg, out=t))
            x = q(np.add(q(np.multiply(f32(0.5), x, out=x)), sm, out=x))
        tgt = f32(self.n / p.k)
        beta = beta.astype(f32)
        for _ in range(p.balance_iters):
            sizes = np.bincount(np.argmax(x * beta[:, None], axis=0), minlength=p.k).astype(f32)
            beta = np.clip(beta * (tgt / np.maximum(sizes, f32(1.0))) ** f32(p.balance_exp),
                           f32(1e-3), f32(1e3)).astype(f32)
        new = np.argmax(x * beta[:, None], axis=0).astype(np.int32)
        return new, (w.T, beta)
