"""Pallas kernel: fused Alg-2 TFS-block placement sweep.

The scheduler's hot path advances a per-row simulation state (device
cursor ``j``, task cursor ``k``, remaining capacity ``c``, carried share
``tsd``) over a block of TFS rows.  The jax backend expresses one step as
~15 gather/where ops XLA schedules independently; here the whole sweep is
*one kernel*: a row tile of the block lives in VMEM, the (tiny) per-task
and per-device tables live in SMEM, and an in-kernel ``fori_loop`` runs
all ``n_t + n_f`` carry/split steps over that tile before it is written
back — no intermediate HBM traffic.

Layout: rows lie along the lanes.  A tile of ``bR`` rows is held as
``(bR // 128, 128)`` arrays — every state vector is lane-dense — and the
shares tile as ``(n_t, bR // 128, 128)``, one dense slab per task column.
Gathers (``iis[k]``, ``t_cfg[j]``, ``shares[row, k]``) are select chains
over the static task/device index: with the cursor clipped into range
exactly one branch survives, so the gathered value is bit-exact in any
float width and nothing lowers to a scatter/gather.

The solo sweep is the instance-batched kernel at one instance: its
effective counts are the full widths, so the same step arithmetic serves
both entry points.

Precision: in interpret mode (off-TPU) the kernel runs at float64 and is
pinned bit-for-bit to ``ref.placement_sweep_ref``, itself pinned to the
scalar Alg-2/Alg-3 oracle.  A TPU has no float64 in Mosaic, so there the
backend lowers the kernel at float32
(``placement_backends.jax_runtime.pallas_precision``).  Nothing bounds
how far a float32 verdict may stray near a capacity threshold; on a TPU
v5 lite the chip smoke (``chip_smoke.py``) found every plan identical to
the numpy float64 engine's and the scalar oracle's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace
from .ref import _PLACE_EPS

__all__ = ["placement_sweep_pallas", "placement_sweep_batch_pallas"]

LANES = 128


def _pick(idx, values):
    """Exact gather ``values[idx]`` for an index already clipped in range."""
    out = values[0]
    for i in range(1, len(values)):
        out = jnp.where(idx == i, values[i], out)
    return out


def _placement_sweep_kernel(
    iis_ref,  # SMEM (B * n_t,)
    slr_ref,  # SMEM (B * n_f,)
    cfg_ref,  # SMEM (B * n_f,)
    eff_ref,  # SMEM (B * 2,) int32 — [n_t_eff, n_f_eff] per instance
    resume_ref,  # SMEM (1,) — t_capture + t_store (traced, no recompiles)
    shares_ref,  # VMEM (1, n_t, S, 128) — one instance's row tile
    feas_ref,  # VMEM (1, S, 128) int32 out
    placed_ref,
    splits_ref,
    devused_ref,
    *,
    n_t: int,
    n_f: int,
    repay_init: bool,
):
    """One grid cell: the full ``n_t + n_f``-step sweep of one row tile.

    The grid is ``(B, Rp // bR)``: axis 0 walks instances (each cell reads
    its own task/device tables and effective counts from SMEM), axis 1
    walks row tiles.  Padded task columns / device slots beyond the
    instance's effective counts are never read, so a live row replays the
    unpadded sweep's add/sub chain exactly.
    """
    b = pl.program_id(0)
    iis = [iis_ref[b * n_t + t] for t in range(n_t)]
    slr = [slr_ref[b * n_f + f] for f in range(n_f)]
    cfg = [cfg_ref[b * n_f + f] for f in range(n_f)]
    n_t_eff = eff_ref[2 * b]
    n_f_eff = eff_ref[2 * b + 1]
    resume_cost = resume_ref[0]
    shares = [shares_ref[0, t] for t in range(n_t)]
    shape = shares[0].shape
    dt = shares[0].dtype

    # Mosaic lays an int splat constant out replicated, and a loop carry
    # keeps the layout of its initial value; a zero loaded back from VMEM
    # has the ordinary lane-dense layout the step's results take.
    placed_ref[0] = jnp.zeros(shape, jnp.int32)
    zeros_i = placed_ref[0]
    state = (
        zeros_i,  # j — device cursor
        zeros_i,  # k — task cursor
        jnp.full(shape, slr[0], dtype=dt),  # c — remaining capacity
        jnp.zeros(shape, dt),  # tsd — carried share of task k
        zeros_i,  # dead (0/1)
        zeros_i,  # n_splits
        zeros_i,  # devices_used
    )

    def step(_, state):
        j, k, c, tsd, dead, n_splits, devices_used = state
        live = (dead == 0) & (k < n_t_eff)
        kk = jnp.minimum(k, n_t - 1)
        jj = jnp.minimum(j, n_f - 1)
        ii = _pick(kk, iis)
        tcfg = _pick(jj, cfg)
        carried = tsd > _PLACE_EPS
        extra = jnp.where(carried, ii if repay_init else resume_cost, 0.0)
        rem = _pick(kk, shares) - tsd
        avail = (c - tcfg) - extra
        can_start = (c > tcfg + ii + _PLACE_EPS) & (avail > _PLACE_EPS) & live
        split = can_start & (rem - avail > _PLACE_EPS)
        fits = can_start & ~split

        devices_used = jnp.where(
            can_start, jnp.maximum(devices_used, jj + 1), devices_used
        )
        tsd = jnp.where(split, tsd + avail, tsd)
        n_splits = n_splits + (split & ~carried).astype(jnp.int32)

        c_after = avail - rem
        closure = fits & (c_after <= tcfg + ii + _PLACE_EPS)
        c = jnp.where(fits, c_after, c)
        k = k + fits.astype(jnp.int32)
        tsd = jnp.where(fits, 0.0, tsd)

        advance = (~can_start | split | closure) & live
        j_next = j + advance.astype(jnp.int32)
        overflow = advance & (j_next >= n_f_eff) & (k < n_t_eff)
        dead = dead | overflow.astype(jnp.int32)
        refill = advance & (j_next < n_f_eff)
        c = jnp.where(refill, _pick(jnp.minimum(j_next, n_f - 1), slr), c)
        return (j_next, k, c, tsd, dead, n_splits, devices_used)

    _, k, _, _, dead, n_splits, devices_used = jax.lax.fori_loop(
        0, n_t + n_f, step, state
    )
    feas_ref[0] = ((k >= n_t_eff) & (dead == 0)).astype(jnp.int32)
    placed_ref[0] = k
    splits_ref[0] = n_splits
    devused_ref[0] = devices_used


def _pow2(n: int, floor: int) -> int:
    """Next power of two >= n, at least ``floor``."""
    p = floor
    while p < n:
        p <<= 1
    return p


def placement_sweep_pallas(
    shares: jax.Array,  # (B, n_t)
    iis: jax.Array,  # (n_t,)
    t_slr: jax.Array,  # (n_f,)
    t_cfg: jax.Array,  # (n_f,)
    *,
    resume_cost=0.0,  # traced scalar: t_capture + t_store
    repay_init: bool = True,
    block_rows: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused block placement sweep; same contract as
    ``ref.placement_sweep_ref``.

    The instance-batched kernel at one instance.  Degenerate
    ``n_t == 0`` / ``n_f == 0`` blocks are the caller's job (see
    ``placement_backends.base``).
    """
    n_t, n_f = shares.shape[1], t_slr.shape[0]
    outs = placement_sweep_batch_pallas(
        shares[None],
        iis[None],
        t_slr[None],
        t_cfg[None],
        np.full(1, n_t, np.int32),
        np.full(1, n_f, np.int32),
        resume_cost=resume_cost,
        repay_init=repay_init,
        block_rows=block_rows,
        interpret=interpret,
    )
    # Dropping the instance axis: one eager device op an output.
    with trace.span("sched.unbatch", "unbatch_us"):
        return tuple(o[0] for o in outs)


def placement_sweep_batch_pallas(
    shares: jax.Array,  # (B, R, n_t) — stacked, padded instance blocks
    iis: jax.Array,  # (B, n_t)
    t_slr: jax.Array,  # (B, n_f)
    t_cfg: jax.Array,  # (B, n_f)
    n_t_eff: jax.Array,  # (B,) int
    n_f_eff: jax.Array,  # (B,) int
    *,
    resume_cost=0.0,
    repay_init: bool = True,
    block_rows: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fleet-parallel fused sweep; same contract as
    ``ref.placement_sweep_batch_ref``.

    One ``pallas_call`` sweeps every instance's block: the grid's leading
    axis walks instances, each cell streaming one ``block_rows`` row tile
    of one instance through VMEM.  Instances and rows are padded to the
    next power of two (rows to >= 128) outside the jit boundary, so
    distinct (B, R) batch shapes — a batched walk's live instance count
    shrinks round by round — collapse onto O(log B · log R) compiled
    specializations per (n_t, n_f) topology.  Padded instances carry
    ``n_t_eff == 0`` and, like padded rows, trivially "place"; both are
    sliced off here.
    """
    B, R, n_t = shares.shape
    if block_rows < LANES or block_rows & (block_rows - 1):
        raise ValueError(
            f"block_rows={block_rows} must be a power of two >= {LANES}"
        )
    Bp, Rp = _pow2(B, 1), _pow2(R, LANES)
    # Pad where the arrays live: host arrays from the backends stay on the
    # host, so no eager device op compiles per distinct (B, R).
    xp = np if isinstance(shares, np.ndarray) else jnp
    with trace.span("sched.prepare", "prepare_us"):
        shares = xp.pad(shares, ((0, Bp - B), (0, Rp - R), (0, 0)))
        iis, t_slr, t_cfg = (
            xp.pad(a, ((0, Bp - B), (0, 0))) for a in (iis, t_slr, t_cfg)
        )
        eff = xp.pad(
            xp.stack([xp.asarray(n_t_eff), xp.asarray(n_f_eff)], axis=1).astype(xp.int32),
            ((0, Bp - B), (0, 0)),
        )
    trace.note(padded_rows=Rp)
    # Host arrays are copied to the device by the call.
    with trace.launch((shares, iis, t_slr, t_cfg, eff) if xp is np else ()):
        outs = _placement_sweep_batch_padded(
            shares, iis, t_slr, t_cfg, eff, resume_cost,
            repay_init=repay_init, block_rows=block_rows, interpret=interpret,
        )
        return tuple(o[:B, :R] for o in outs)


@functools.partial(
    jax.jit,
    static_argnames=("repay_init", "block_rows", "interpret"),
)
def _placement_sweep_batch_padded(
    shares: jax.Array,  # (B, Rp, n_t) — Rp a power of two >= 128
    iis: jax.Array,
    t_slr: jax.Array,
    t_cfg: jax.Array,
    eff: jax.Array,  # (B, 2) int32
    resume_cost,
    *,
    repay_init: bool,
    block_rows: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    B, Rp, n_t = shares.shape
    n_f = t_slr.shape[1]
    dt = shares.dtype
    # Rp and bR are both powers of two >= 128, so tiles divide exactly.
    bR = min(block_rows, Rp)
    S, Sp = bR // LANES, Rp // LANES
    # Rows onto lanes: (B, Rp, n_t) -> (B, n_t, Rp // 128, 128).
    shares_t = jnp.swapaxes(shares, 1, 2).reshape(B, n_t, Sp, LANES)

    kernel = functools.partial(
        _placement_sweep_kernel, n_t=n_t, n_f=n_f, repay_init=repay_init
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((1, S, LANES), lambda b, r: (b, r, 0))
    feas, placed, n_splits, devices_used = pl.pallas_call(
        kernel,
        grid=(B, Sp // S),
        in_specs=[smem] * 5
        + [pl.BlockSpec((1, n_t, S, LANES), lambda b, r: (b, 0, r, 0))],
        out_specs=[out_spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((B, Sp, LANES), jnp.int32)] * 4,
        interpret=interpret,
    )(
        iis.astype(dt).reshape(-1),
        t_slr.astype(dt).reshape(-1),
        t_cfg.astype(dt).reshape(-1),
        eff.reshape(-1),
        jnp.asarray(resume_cost, dtype=dt).reshape(1),
        shares_t,
    )
    return (
        feas.reshape(B, Rp).astype(bool),
        placed.reshape(B, Rp),
        n_splits.reshape(B, Rp),
        devices_used.reshape(B, Rp),
    )
