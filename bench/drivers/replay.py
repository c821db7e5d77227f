"""Cold or resident replay of fresh evaluation logs, one after another
(``PartitionedGraphService.run_ops``); the mix's ``resident`` says which."""

from __future__ import annotations

import time
from typing import List

import numpy as np

from bench.drivers.base import Driver, check_entry, counters, rng_for
from bench.reference import oracle


class Replay(Driver):
    def setup(self) -> None:
        svc = self.build_service()
        self.logs = self.op_source()
        with self.spans("warmup"):
            svc.run_ops(self.oplog(*next(self.logs)), resident=self.mix["resident"])
        self.span_engine()

    def window(self, seconds: float) -> dict:
        """Replays logs until ``seconds`` have passed. The results of
        ``reference_logs`` of them, a uniform sample drawn from the seed,
        are kept for the check (reservoir sampling; the rest are freed)."""
        svc, resident = self.svc, self.mix["resident"]
        keep, rng = self.config["reference_logs"], rng_for(self.seed, "check")
        self.kept: List[tuple] = []
        n_ops = n_logs = 0
        t0 = time.perf_counter()
        while True:
            starts, ends = next(self.logs)
            with self.spans("log"):
                res = svc.run_ops(self.oplog(starts, ends), resident=resident)
            slot = n_logs if n_logs < keep else int(rng.integers(n_logs + 1))
            if slot < keep:
                self.kept[slot:slot + 1] = [(starts, ends, counters(res))]
            n_logs += 1
            n_ops += starts.shape[0]
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.parts = np.array(svc.parts, copy=True)
        self.attempted = n_ops
        self.samples.update(ops=n_ops, logs=n_logs, window_s=elapsed)
        return {"ops_per_s": n_ops / elapsed}

    def check(self) -> dict:
        ref = self.reference()
        bad = sum(oracle.mismatches(got, ref.counters(self.parts, starts, ends))[0]
                  for starts, ends, got in self.kept)
        return {"counter_mismatches": check_entry(bad, 0, bad == 0)}

    def plant_control(self) -> None:
        """The control's counters in place of the program's, on the logs
        the check compares."""
        ctl = self.reference(control=True)
        self.kept = [(s, e, ctl.counters(self.parts, s, e)) for s, e, _ in self.kept]


DRIVER = Replay
