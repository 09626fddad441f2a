"""Pallas block-placement backend — the whole carry/split sweep fused.

Wraps :func:`repro.kernels.ops.placement_sweep`: row tiles of the TFS
block stream through VMEM and an in-kernel ``fori_loop`` runs all
``n_t + n_f`` placement steps per tile in one fused kernel — no
intermediate HBM round-trips between steps, so ~10^6-row blocks sweep
per call.  Off-TPU the kernel executes in Pallas interpret mode (correct
but slow — useful for parity testing, not throughput; ``"auto"`` only
selects this backend on a TPU host).

Off-TPU the kernel interprets at float64 under the same scoped ``x64``
as the jax backend, so its verdicts are bit-identical to the scalar
oracle.  Mosaic has no float64, so on a TPU the kernel lowers at float32
(``jax_runtime.pallas_precision``).  Nothing in the kernel bounds how far
a float32 verdict may stray from the oracle's near a capacity threshold.
On a TPU v5 lite every plan of ``chip_smoke.py`` (paper Example 1, the
425k-row deep instance at k=0 and k=1, 64 batched instances, a 20-event
service trace) was identical to the numpy engine's and the scalar
oracle's; ``resolve_engine("auto")`` picks this engine on a TPU only
while that holds.

Fleet-parallel batching: ``dispatch_blocks`` wraps the grid-extended
kernel (:func:`repro.kernels.ops.placement_sweep_batch`) — the pallas
grid gains a leading instance axis, so one kernel launch sweeps every
instance's block with its own task/device tables.  ``shard`` is accepted
and ignored: a pallas_call runs on one device, and instance-axis device
layout is the jax backend's ``shard_map`` job (see ``base.py``).
"""

from __future__ import annotations

import numpy as np

from ... import trace
from .base import (
    BatchPlacement,
    InstanceBatch,
    PlacementOptions,
    fetch,
    place_instance_blocks,
    prepare_block,
    register_backend,
    survivor_batch_tables,
    survivor_tables,
)
from .jax_runtime import configure_compile_cache, pallas_precision

__all__ = ["PallasPlacementBackend"]


@register_backend("pallas")
class PallasPlacementBackend:
    """Fused single-kernel sweep (interpret mode off-TPU)."""

    name = "pallas"
    async_dispatch = True

    def __init__(self, block_rows: int = 1024) -> None:
        self.block_rows = block_rows
        configure_compile_cache()

    @classmethod
    def available(cls) -> bool:
        try:
            from jax.experimental import pallas  # noqa: F401
        except ImportError:
            return False
        return True

    def dispatch_block(
        self,
        shares: np.ndarray,
        iis: np.ndarray,
        t_slr: np.ndarray,
        t_cfg: np.ndarray,
        opts: PlacementOptions | None = None,
    ):
        """Enqueue the fused kernel; the returned resolver syncs verdicts.

        On TPU the pallas_call dispatches asynchronously like any jit'd
        computation, so the walk's double buffering overlaps the next
        block's enumeration with this sweep; in interpret mode execution
        is eager and the resolver just repackages (see ``base.py``).
        """
        with trace.span("sched.prepare", "prepare_us"):
            shares, iis, t_slr_arr, t_cfg_arr, opts, early = prepare_block(
                shares, iis, t_slr, t_cfg, opts
            )
            if early is not None:
                return lambda: early
            from repro.kernels.ops import placement_sweep

            # Survivor tables are selected at float64 (the lexsort that picks
            # the worst-case adversary must match the other backends) before
            # any TPU float32 cast.
            surv = None
            if opts.resilience:
                surv = survivor_tables(t_slr_arr, t_cfg_arr, opts.resilience)
            dtype, precision_ctx = pallas_precision()
            shares, iis, t_slr_arr, t_cfg_arr = (
                a.astype(dtype, copy=False) for a in (shares, iis, t_slr_arr, t_cfg_arr)
            )
            if surv is not None:
                surv = tuple(a.astype(dtype, copy=False) for a in surv)
        with precision_ctx:
            outs = placement_sweep(
                shares,
                iis,
                t_slr_arr,
                t_cfg_arr,
                resume_cost=opts.resume_cost,
                repay_init=opts.repay_init,
                block_rows=self.block_rows,
            )
            outs_s = None
            if surv is not None:
                # Second, constrained pass: same rows on the worst-case
                # survivor fleet, enqueued back-to-back so both kernels
                # overlap the walk's next-block enumeration.
                outs_s = placement_sweep(
                    shares,
                    iis,
                    surv[0],
                    surv[1],
                    resume_cost=opts.resume_cost,
                    repay_init=opts.repay_init,
                    block_rows=self.block_rows,
                )

        def resolve() -> BatchPlacement:
            out = fetch(outs)
            feasible = out[0].astype(bool)
            if outs_s is not None:
                feasible = feasible & fetch(outs_s[:1])[0].astype(bool)
            return BatchPlacement(
                feasible=feasible,
                placed_tasks=out[1].astype(np.int64),
                n_splits=out[2].astype(np.int64),
                devices_used=out[3].astype(np.int64),
            )

        return resolve

    def place_block(
        self,
        shares: np.ndarray,
        iis: np.ndarray,
        t_slr: np.ndarray,
        t_cfg: np.ndarray,
        opts: PlacementOptions | None = None,
    ) -> BatchPlacement:
        return self.dispatch_block(shares, iis, t_slr, t_cfg, opts)()

    def dispatch_blocks_raw(
        self,
        batch: InstanceBatch,
        opts: PlacementOptions | None = None,
        *,
        shard=None,
    ):
        """Enqueue the grid-extended launch; resolver returns raw arrays.

        Same raw batching contract as the jax backend (see ``base.py``):
        the resolver yields the four untrimmed ``(B', Rp)`` verdict
        arrays, and ``None`` signals a degenerate batch the kernel cannot
        express (callers fall back to the per-instance surface).
        ``shard`` is ignored: sharding the instance axis is ``shard_map``
        territory (engine="jax"); a single kernel launch lives on one
        device.
        """
        B = len(batch)
        if B == 0:
            return None
        if opts is None:
            opts = PlacementOptions()
        if batch.shares.shape[2] == 0 or batch.t_slr.shape[1] == 0:
            # Zero-width task/device tables cannot flow through the kernel;
            # prepare_block's early paths answer every instance.
            return None
        from repro.kernels.ops import placement_sweep_batch

        with trace.span("sched.prepare", "prepare_us"):
            surv = None
            if opts.resilience:
                # Per-instance worst-case survivor tables, selected at float64
                # before any TPU cast (see dispatch_block).
                surv = survivor_batch_tables(
                    batch.t_slr, batch.t_cfg, batch.n_f_eff, opts.resilience
                )
            dtype, precision_ctx = pallas_precision()
            shares, iis, t_slr, t_cfg = (
                a.astype(dtype, copy=False)
                for a in (batch.shares, batch.iis, batch.t_slr, batch.t_cfg)
            )
            if surv is not None:
                surv = (
                    surv[0].astype(dtype, copy=False),
                    surv[1].astype(dtype, copy=False),
                    surv[2],
                )
        with precision_ctx:
            outs = placement_sweep_batch(
                shares,
                iis,
                t_slr,
                t_cfg,
                batch.n_t_eff,
                batch.n_f_eff,
                resume_cost=opts.resume_cost,
                repay_init=opts.repay_init,
                block_rows=self.block_rows,
            )
            outs_s = None
            if surv is not None:
                outs_s = placement_sweep_batch(
                    shares,
                    iis,
                    surv[0],
                    surv[1],
                    batch.n_t_eff,
                    surv[2],
                    resume_cost=opts.resume_cost,
                    repay_init=opts.repay_init,
                    block_rows=self.block_rows,
                )

        def resolve_raw():
            feas, placed, n_splits, devices_used = fetch(outs)
            if outs_s is not None:
                feas = feas.astype(bool) & fetch(outs_s[:1])[0].astype(bool)
            return feas, placed, n_splits, devices_used

        return resolve_raw

    def dispatch_blocks(
        self,
        batch: InstanceBatch,
        opts: PlacementOptions | None = None,
        *,
        shard=None,
    ):
        """Enqueue one grid-extended kernel launch over all B instances.

        The grid's leading axis walks instances, so every instance's
        block sweeps in the same ``pallas_call`` — resolver contract as
        the jax backend's (trimmed per-instance verdicts, bit-identical
        to the numpy loop reference in interpret mode).
        """
        B = len(batch)
        if B == 0:
            return lambda: []
        raw = self.dispatch_blocks_raw(batch, opts, shard=shard)
        if raw is None:
            result = place_instance_blocks(
                self, batch, opts if opts is not None else PlacementOptions()
            )
            return lambda: result

        def resolve() -> list[BatchPlacement]:
            feas, placed, n_splits, devices_used = raw()
            out = []
            for i in range(B):
                r = int(batch.n_rows[i])
                out.append(
                    BatchPlacement(
                        feasible=feas[i, :r].astype(bool),
                        placed_tasks=placed[i, :r].astype(np.int64),
                        n_splits=n_splits[i, :r].astype(np.int64),
                        devices_used=devices_used[i, :r].astype(np.int64),
                    )
                )
            return out

        return resolve

    def place_blocks(
        self,
        batch: InstanceBatch,
        opts: PlacementOptions | None = None,
        *,
        shard=None,
    ) -> list[BatchPlacement]:
        return self.dispatch_blocks(batch, opts, shard=shard)()
