"""The paper's Dynamic experiment: per slice a random-move dynamism log,
one DiDiC repair iteration, migration, then a resident replay of the
evaluation log, driven through ``DynamicExperimentRuntime``."""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench.drivers.base import Driver, check_entry, counters, rng_for
from bench.reference import didic as ref_didic
from bench.reference import graphs, oplogs, oracle


class Dynamic(Driver):
    WARMUP_SLICES = 2

    def setup(self) -> None:
        from repro.core.dynamic_runtime import DynamicExperimentRuntime

        svc = self.build_service()
        # The evaluation log as replayed, kept whole for the check (a
        # target search stops where it finds its target).
        self.log = next(self.op_source())
        self.ops = self.oplog(*self.log)
        self.moves_rng = rng_for(self.seed, "dynamism")
        self.runtime = DynamicExperimentRuntime(svc, insert_method="random", seed=0)
        self.runtime.begin(self.ops)
        # maps[i] is the map served before slice i, repaired[i] the map the
        # repair of slice i served after it.
        self.maps = [np.array(svc.parts, copy=True)]
        self.repaired: List[np.ndarray] = []
        self.moves: List[tuple] = []
        self.results: List[Dict[str, np.ndarray]] = []
        self.spans.wrap(svc, "apply_dynamism", "service.apply_dynamism")
        self.spans.wrap(svc, "maintain_migrate", "service.maintain_migrate")
        self.spans.wrap(svc, "run_ops", "service.run_ops")
        # The second slice is the first to repair from a carried DiDiC
        # state, which compiles the step once more.
        with self.spans("warmup"):
            for i in range(self.WARMUP_SLICES):
                self._slice(i)
        self.span_engine()

    def _slice(self, i: int) -> None:
        from repro.core.dynamism import DynamismLog

        c = self.config
        v, t = oplogs.random_moves(self.svc.graph.n_nodes, self.mix["amount"], c["k"],
                                   self.moves_rng)
        self.moves.append((v, t))
        _, res = self.runtime.run_slice(
            i, self.ops, self.mix["amount"], maintain_every=1,
            iterations=c["repair_iterations"], log=DynamismLog(v, t, "random", c["k"]))
        served = np.array(self.svc.parts, copy=True)
        self.maps.append(served)
        self.repaired.append(served)
        self.results.append(counters(res))

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        slices = 0
        while True:
            with self.spans("slice"):
                self._slice(self.WARMUP_SLICES + slices)
            slices += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.slices = slices
        self.attempted = slices
        c, d = self.config, self.config["didic"]
        s, _, _ = graphs.symmetrize(self.edges)
        self.samples.update(
            slices=slices, window_s=elapsed, iterations=slices * c["repair_iterations"],
            spmm_per_iteration=d["primary_steps"] * (d["secondary_steps"] + 1) + d["smooth_cap"],
            sym_edges=int(s.shape[0]), n_nodes=self.edges.n_nodes, k=c["k"],
        )
        return {"slice_s": elapsed / slices}

    def _last_checked(self) -> int:
        """The last window slice whose repair the check compares, drawn from
        the seed."""
        return self.WARMUP_SLICES + int(rng_for(self.seed, "check").integers(self.slices))

    def reference_repair(self, precision: str = "float32"):
        """The repaired maps of the warm-up slices and of the window slices
        up to the one the check draws, each repaired from the map the
        program served before it plus that slice's moves; the carried state
        (w, β) is the reference's own."""
        c, d = self.config, self.config["didic"]
        s, r, w = graphs.symmetrize(self.edges)
        repair = ref_didic.DidicRepair(
            s, r, w, self.edges.n_nodes,
            ref_didic.DidicParams(k=c["k"], primary_steps=d["primary_steps"],
                                  secondary_steps=d["secondary_steps"],
                                  smoothing_steps=d["smooth_cap"],
                                  balance_iters=d["balance_iters"],
                                  balance_exp=d["balance_exp"]),
            precision=precision)
        state, maps = None, []
        for i in range(self._last_checked() + 1):
            parts_in = oplogs.apply_moves(self.maps[i], *self.moves[i])
            for _ in range(c["repair_iterations"]):
                parts_in, state = repair.iterate(parts_in, state)
            maps.append(parts_in)
        return maps

    def check(self) -> dict:
        maps = self.reference_repair()
        first = self.WARMUP_SLICES
        share = max(float(np.mean(maps[i] != self.repaired[i])) for i in range(first, len(maps)))
        ref = self.reference()
        bad = 0
        for i in range(first, first + self.slices):
            want = ref.counters(self.maps[i + 1], *self.log)
            bad += oracle.mismatches(self.results[i], want)[0]
        limit = self.config["repair_mismatch_limit"]
        return {
            "repair_mismatch_share": check_entry(share, limit, share <= limit),
            "counter_mismatches": check_entry(bad, 0, bad == 0),
        }

    def plant_control(self) -> None:
        """The control in the program's place: the bfloat16 reference's
        repairs of the slices the check compares, and the dataset control's
        counters of every window slice."""
        maps = self.reference_repair("bfloat16")
        for i in range(self.WARMUP_SLICES, len(maps)):
            self.repaired[i] = maps[i]
        ctl = self.reference(control=True)
        for i in range(self.WARMUP_SLICES, self.WARMUP_SLICES + self.slices):
            self.results[i] = ctl.counters(self.maps[i + 1], *self.log)


DRIVER = Dynamic
