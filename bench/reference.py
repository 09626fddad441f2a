"""Plain reference for PADPS-FR (arXiv:2311.11015, Algorithms 1-3).

Written from the paper and imports nothing of the program under test:

* Alg 1: every combination of one variant per task (the TSS, C order of
  variant indices), eq. 5 shares ``td / th / p * t_slr``, and the eq-7
  workability filter ``sum_shr <= n_f * t_slr - (n_t + 1) * t_cfg``
  (with 1e-9 slack), which keeps the TFS.
* Alg 2: the TFS in ascending total power, ties by TSS index; the first
  row that places on the fleet wins.  A row places when every task's
  share (which includes one initialisation interval II) fits in device
  order: a task starts only where the capacity left exceeds
  ``t_cfg + II``, splits its remainder onto the next device (paying
  ``t_cfg`` and a fresh II there), and a device closes once what is left
  is within ``t_cfg + II`` of the task just placed.
* Alg 3: the winner's per-device script (cfg / init / run / null
  segments) and its data splits.

Alg 1 and Alg 2 run with numpy at a stated dtype, the verdicts over
blocks of rows: float64 is the reference; a lower dtype gives the
control that the correctness check has to reject.  The chosen row's
script (Alg 3) is a scalar float64 walk, as the program materialises its
winner.  Sums are left folds in task order.
"""

from __future__ import annotations

import numpy as np

EPS = 1e-9
BLOCK = 8192


def shares(task: dict, t_slr: float) -> list[float]:
    """Eq. 5 for each variant of ``task`` (keys: data, period, throughput)."""
    return [task["data"] / th / task["period"] * t_slr for th in task["throughput"]]


def _fold(vectors: list[np.ndarray]) -> np.ndarray:
    """Left-fold sum over the Cartesian product, flat in C order."""
    out = vectors[0]
    for v in vectors[1:]:
        out = (out[:, None] + v[None, :]).reshape(-1)
    return out


def _verdicts(rows: np.ndarray, iis: np.ndarray, n_f: int, t_slr: float,
              t_cfg: float, dtype) -> np.ndarray:
    """Alg-2 placeability of each row of ``rows`` (R, n_t) at ``dtype``."""
    R, n_t = rows.shape
    sh = rows.astype(dtype)
    ii_t = iis.astype(dtype)
    slr = np.asarray(t_slr, dtype=dtype)
    cfg = np.asarray(t_cfg, dtype=dtype)
    eps = np.asarray(EPS, dtype=dtype)
    zero = np.asarray(0.0, dtype=dtype)
    j = np.zeros(R, dtype=np.int64)
    k = np.zeros(R, dtype=np.int64)
    c = np.full(R, slr, dtype=dtype)
    tsd = np.zeros(R, dtype=dtype)
    idx = np.arange(R)
    while True:
        act = (k < n_t) & (j < n_f)
        if not act.any():
            break
        a = np.flatnonzero(act)
        ka = k[a]
        ii = ii_t[ka]
        tsd_a, c_a = tsd[a], c[a]
        rem = sh[idx[a], ka] - tsd_a
        extra = np.where(tsd_a > eps, ii, zero)
        avail = c_a - cfg - extra
        ok = (c_a > cfg + ii + eps) & (avail > eps)
        split = ok & (rem - avail > eps)
        full = ok & ~split
        # A split carries the remainder to the next device.
        tsd[a[split]] = tsd_a[split] + avail[split]
        # A task that fits: place it; the device stays open only while
        # more than t_cfg + II of the placed task is left.
        left = avail - rem
        fa = a[full]
        c[fa] = left[full]
        k[fa] += 1
        tsd[fa] = zero
        closed = full & (left <= cfg + ii + eps)
        nxt = a[~ok | split | closed]
        j[nxt] += 1
        c[nxt] = slr
    return (k >= n_t) & (tsd <= eps)


def script(row: list[float], iis: list[float], n_f: int, t_slr: float,
           t_cfg: float) -> dict:
    """Alg 3: the per-device script and data splits of one row (float64)."""
    n_t = len(row)
    devices = []
    splits: dict[int, list] = {}
    k, tsd = 0, 0.0
    for j in range(n_f):
        if k >= n_t:
            break
        c, t, segs = t_slr, 0.0, []
        while k < n_t:
            ii = iis[k]
            rem = row[k] - tsd
            carried = tsd > EPS
            extra = ii if carried else 0.0
            if not c > t_cfg + ii + EPS:
                break
            avail = c - t_cfg - extra
            if avail <= EPS:
                break
            segs.append(("cfg", k, t, t + t_cfg))
            t += t_cfg
            if carried and extra > 0:
                segs.append(("init", k, t, t + extra))
                t += extra
            if rem - avail > EPS:
                segs.append(("run", k, t, t + avail))
                t += avail
                splits.setdefault(k, []).append((j, avail))
                tsd += avail
                break
            segs.append(("run", k, t, t + rem))
            t += rem
            if carried:
                splits.setdefault(k, []).append((j, rem))
            c = avail - rem
            k += 1
            tsd = 0.0
            if c <= t_cfg + ii + EPS:
                break
        if t < t_slr - EPS:
            segs.append(("null", -1, t, t_slr))
        devices.append(tuple(segs))
    devices += [()] * (n_f - len(devices))
    return {
        "feasible": k >= n_t and tsd <= EPS,
        "devices": tuple(devices),
        "splits": tuple(
            (ti, tuple(d for d, _ in parts), tuple(p for _, p in parts))
            for ti, parts in sorted(splits.items())
        ),
    }


def tfs_count(tasks: list[dict], n_f: int, t_slr: float, t_cfg: float) -> int:
    """|TFS| of an instance: the combinations eq. 7 keeps (Alg 1)."""
    sum_shr = _fold([np.asarray(shares(t, t_slr)) for t in tasks])
    return int(np.count_nonzero(sum_shr <= n_f * t_slr - (len(tasks) + 1) * t_cfg + EPS))


def solve(tasks: list[dict], n_f: int, t_slr: float, t_cfg: float,
          dtype=np.float64) -> dict:
    """Alg 1 + 2 + 3 on one instance; Alg 1 and 2 at ``dtype``.

    ``tasks`` are dicts with ``data``, ``period``, ``ii`` and per-variant
    ``throughput`` and ``power`` lists, in the instance's task order.
    """
    n_t = len(tasks)
    share_v = [np.asarray(shares(t, t_slr)) for t in tasks]
    power_v = [np.asarray(t["power"], dtype=np.float64) for t in tasks]
    nvs = [len(v) for v in share_v]
    sum_shr = _fold([v.astype(dtype) for v in share_v])
    power = _fold([v.astype(dtype) for v in power_v])
    budget = np.asarray(n_f * t_slr - (n_t + 1) * t_cfg, dtype=dtype)
    tfs = np.flatnonzero(sum_shr <= budget + np.asarray(EPS, dtype=dtype))
    order = tfs[np.argsort(power[tfs], kind="stable")]
    iis = np.asarray([t["ii"] for t in tasks], dtype=np.float64)
    out = {"n_tss": int(sum_shr.size), "n_tfs": int(tfs.size), "feasible": False,
           "rank": -1, "rejects": int(tfs.size), "variant_idx": None,
           "total_power": float("inf"), "shares": None, "devices": None,
           "splits": None}
    for lo in range(0, order.size, BLOCK):
        flat = order[lo:lo + BLOCK]
        vidx = np.unravel_index(flat, nvs)
        rows = np.stack([share_v[i][vidx[i]] for i in range(n_t)], axis=1)
        ok = np.flatnonzero(_verdicts(rows, iis, n_f, t_slr, t_cfg, dtype))
        if ok.size:
            r = int(ok[0])
            choice = tuple(int(v[r]) for v in vidx)
            row = [float(share_v[i][choice[i]]) for i in range(n_t)]
            total = 0.0
            for i in range(n_t):
                total += float(power_v[i][choice[i]])
            plan = script(row, iis.tolist(), n_f, t_slr, t_cfg)
            out.update(rank=lo + r, rejects=lo + r, variant_idx=choice,
                       total_power=total, shares=tuple(row),
                       feasible=plan["feasible"], devices=plan["devices"],
                       splits=plan["splits"])
            break
    return out
