"""Solve mixes: ``PADPSFRScheduler.schedule``, one instance at a time.

Set-up draws ``draws`` instances of the configuration's task mix from
the seed (more only where fewer than ``panel`` land in the band), solves
each with the program and keeps the first ``panel`` whose winner lies in
the mix's ``rank_band``; a fixed count of draws keeps set-up's work the
same from seed to seed.  Those solves are also the warm-up:
every instance kept had its block shapes compiled by its own solve.  A
pass of the window solves the panel once, in order.
"""

from __future__ import annotations

import time

from bench import generate, program


class Cell:
    unit = "solve"

    def __init__(self, ctx) -> None:
        from repro.core import PADPSFRScheduler, WalkStats

        cfg, mix = ctx.config, ctx.traffic
        self.fleet = generate.fleet(cfg)
        self.n_f, self.t_slr, self.t_cfg = self.fleet
        self._sched = PADPSFRScheduler(
            program.fleet(self.n_f, self.t_slr, self.t_cfg), engine=mix["engine"]
        )
        self._span = ctx.span
        self._walk_stats = WalkStats
        gen = generate.rng(ctx.seed)
        band, want, limit = mix["rank_band"], int(mix["panel"]), int(mix["max_draws"])
        least = int(mix["draws"])
        self.instances: list[list[dict]] = []
        self._panel = []
        draws = rows = blocks = 0
        t0 = time.perf_counter()
        while len(self.instances) < want or draws < least:
            if draws == limit:
                raise RuntimeError(
                    f"{len(self.instances)} of {draws} draws lay in rank band {band}"
                )
            drawn = generate.mix_tasks(cfg, gen)
            draws += 1
            tasks = program.tasks(drawn)
            ws = WalkStats()
            with ctx.span("select"):
                res = self._sched.schedule(tasks, walk_stats=ws)
            if (res.feasible and generate.in_band(res.chosen_rank, band)
                    and len(self.instances) < want):
                self.instances.append(drawn)
                self._panel.append(tasks)
                rows += ws.rows
                blocks += len(ws.block_sizes)
        ctx.parts["select_s"] = time.perf_counter() - t0
        ctx.parts["draws"] = draws
        ctx.parts["accepted"] = len(self.instances)
        ctx.parts["panel_rows"] = rows
        ctx.parts["panel_blocks"] = blocks
        self.n_t = len(self.instances[0])
        self.per_pass = want
        self.reset()

    def reset(self) -> None:
        self.stats = self._walk_stats()
        self.call_s = 0.0

    def run_pass(self) -> list:
        out = []
        now = time.perf_counter
        for tasks in self._panel:
            with self._span("solve"):
                t0 = now()
                res = self._sched.schedule(tasks, walk_stats=self.stats)
                self.call_s += now() - t0
            out.append(res)
        return out

    def require(self) -> None:
        """Nothing beyond the chip check of the harness."""

    def close(self) -> None:
        self._sched = None
        self._panel = []


def setup(ctx) -> Cell:
    return Cell(ctx)
