"""What-if mixes: ``SchedulerService.what_if_many`` against a live fleet.

Set-up builds one live service: the configuration's task mix at its
published data volumes, with the last copy of the mix's ``arrival`` row
left out, submitted task by task.  The live fleet is the same for every
seed, so it fixes most of every instance's |TFS| and the build's work.
Candidate arrivals are copies of that row with data volumes drawn from
the seed, asked about ``batch`` at a time; the first ``batch`` whose
winner lies in ``rank_band`` make the panel (at most ``max_draws`` are
asked).  These calls and one call on the panel itself are the warm-up.
A pass of the window is one ``what_if_many`` call on the panel.

A mix with ``shard`` lays the instance axis over the cell's chips; the
cell refuses to run where it lies over fewer.

``what_if_many`` takes no ``walk_stats``; the service's scheduler is
wrapped so that its batched walk fills the cell's ``WalkStats``.
"""

from __future__ import annotations

import time

from bench import generate, program


class Cell:
    unit = "instance"

    def __init__(self, ctx) -> None:
        from repro.core import WalkStats
        from repro.service import SchedulerService

        cfg, mix = ctx.config, ctx.traffic
        self.fleet = generate.fleet(cfg)
        self.n_f, self.t_slr, self.t_cfg = self.fleet
        self._span = ctx.span
        self._walk_stats = WalkStats
        self._shard = mix.get("shard")
        self._chips = ctx.chips
        want, band, limit = int(mix["batch"]), mix["rank_band"], int(mix["max_draws"])
        row = generate.row_named(cfg, mix["arrival"])
        jitter = float(cfg["data_jitter"])
        gen = generate.rng(ctx.seed)
        self.reset()
        t0 = time.perf_counter()
        live = generate.mix_tasks(cfg, gen, leave_out=mix["arrival"], jitter=0.0)
        svc = SchedulerService(
            program.fleet(self.n_f, self.t_slr, self.t_cfg), engine=mix["engine"]
        )
        with ctx.span("build"):
            if not all(svc.submit(t).admitted for t in program.tasks(live)):
                raise RuntimeError("the published live fleet was not admitted")
        t1 = time.perf_counter()
        ctx.parts["build_s"] = t1 - t0
        self._wrap(svc)
        panel: list[dict] = []
        draws = 0
        while len(panel) < want:
            if draws >= limit:
                raise RuntimeError(f"{len(panel)} of {draws} candidates lay in rank band {band}")
            cands = [generate.jittered(row, f"cand{draws + i}", gen, jitter)
                     for i in range(want)]
            draws += want
            with ctx.span("select"):
                res = svc.what_if_many(program.tasks(cands), shard=self._shard)
            panel += [c for c, r in zip(cands, res, strict=True)
                      if r.feasible and generate.in_band(r.chosen_rank, band)]
        panel = panel[:want]
        ctx.parts["select_s"] = time.perf_counter() - t1
        ctx.parts["draws"] = draws
        ctx.parts["accepted"] = len(panel)
        self._svc = svc
        self._panel = program.tasks(panel)
        self.instances = [live + [c] for c in panel]
        self.n_t = len(self.instances[0])
        self.per_pass = want
        t0 = time.perf_counter()
        self.reset()
        with ctx.span("warmup"):
            self.run_pass()
        ctx.parts["warmup_s"] = time.perf_counter() - t0
        ctx.parts["panel_rows"] = self.stats.rows
        ctx.parts["panel_blocks"] = len(self.stats.block_sizes)
        self.reset()

    def _wrap(self, svc) -> None:
        """Route the service's batched walk through the cell's WalkStats."""
        sched = svc._sched
        inner = sched.schedule_many

        def schedule_many(instances, **kw):
            kw.setdefault("walk_stats", self.stats)
            return inner(instances, **kw)

        sched.schedule_many = schedule_many

    def reset(self) -> None:
        self.stats = self._walk_stats()
        self.call_s = 0.0

    def run_pass(self) -> list:
        with self._span("what_if_many"):
            t0 = time.perf_counter()
            out = self._svc.what_if_many(self._panel, shard=self._shard)
            self.call_s += time.perf_counter() - t0
        return out

    def require(self) -> None:
        """A sharded mix has to lay the instance axis over the cell's chips."""
        if self._shard is None:
            return
        from repro.core.placement_backends.jax_backend import resolve_shard

        bp = 1 << (self.per_pass - 1).bit_length()
        got = resolve_shard(self._shard, bp)
        if got != self._chips:
            raise RuntimeError(f"shard={self._shard!r} lays {bp} instances over {got} "
                               f"devices, not {self._chips}")

    def close(self) -> None:
        self._svc = None
        self._panel = ()


def setup(ctx) -> Cell:
    return Cell(ctx)
