"""The boundary to the program under test.

Everything the benchmark takes from the program passes through here: the
objects its entry points take (tasks, fleets) and the answers they give,
turned into the reference's plain form so that the two compare field by
field.  Nothing here computes a plan.
"""

from __future__ import annotations


def tasks(rows: list[dict]) -> tuple:
    """The program's ``Task`` objects for plain task dicts, in order."""
    from repro.core import Task, TaskVariant

    return tuple(
        Task(
            name=r["name"],
            period=float(r["period"]),
            data=float(r["data"]),
            init_interval=float(r["ii"]),
            variants=tuple(
                TaskVariant(cu=j + 1, throughput=float(th), power=float(pw))
                for j, (th, pw) in enumerate(zip(r["throughput"], r["power"], strict=True))
            ),
        )
        for r in rows
    )


def fleet(n_f: int, t_slr: float, t_cfg: float):
    from repro.core import FleetSpec

    return FleetSpec(n_f=n_f, t_slr=t_slr, t_cfg=t_cfg)


def plain(res) -> dict:
    """One ``ScheduleResult`` in the reference's form (see ``reference.solve``)."""
    out = {"n_tss": res.n_tss, "n_tfs": res.n_tfs, "feasible": bool(res.feasible),
           "rank": res.chosen_rank, "rejects": res.n_placement_rejects,
           "variant_idx": None, "total_power": res.total_power, "shares": None,
           "devices": None, "splits": None}
    if res.feasible:
        plan = res.plan
        out.update(
            variant_idx=tuple(int(v) for v in res.combo.variant_idx),
            shares=tuple(float(s) for s in res.combo.shares),
            devices=tuple(
                tuple((s.kind, s.task, s.start, s.end) for s in d.segments)
                for d in plan.scripts
            ),
            splits=tuple(
                (s.task, tuple(s.devices), tuple(s.share_parts)) for s in plan.splits
            ),
        )
    return out


def differs(got: dict, want: dict) -> list[str]:
    """Fields in which a program answer departs from the reference's.

    Everything is compared exactly.  ``n_tfs`` is compared where the
    program counts it (its streaming enumerator reports -1).
    """
    bad = [k for k in want if k != "n_tfs" and got.get(k) != want[k]]
    if got.get("n_tfs", -1) != -1 and got["n_tfs"] != want["n_tfs"]:
        bad.append("n_tfs")
    return bad
