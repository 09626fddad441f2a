"""JAX block-placement backend — a jit'd ``lax.while_loop`` over (B,) state.

The numpy engine's vectorized carry/split step becomes one XLA program:
the whole per-row simulation state (device cursor, task cursor, remaining
capacity, carried share) is a tuple of ``(B,)`` arrays advanced inside a
``lax.while_loop`` with ``n_t`` / ``n_f`` static, so a TFS block of 10^6
rows sweeps in a single device call with no per-step host round-trip.

Bit-compatibility with the scalar oracle: the step arithmetic (defined
once in :func:`repro.kernels.ref.placement_sweep_ref`) replays the same
float64 add/sub chains in the same order — no multiply-add pairs, so XLA
cannot FMA-contract them — and runs under the scoped
:func:`~repro.core.placement_backends.jax_runtime.x64`, so the global jax
float32 default (which the model/training substrate relies on) is
untouched.  On a TPU the same float64 program runs with XLA's float64
emulation; on a TPU v5 lite every plan of ``chip_smoke.py`` was identical
to the numpy engine's and the scalar oracle's.

Block shapes are padded to the next power of two, bounding recompilation
to O(log B) specializations per (n_t, n_f) topology; padded rows are
sliced off before the verdicts leave the backend.

``dispatch_block`` exposes jax's async dispatch to the scheduler walk:
the jit'd sweep is *enqueued* and a resolver returned; converting the
outputs to numpy (the only blocking step) happens when the walk calls
it, one block later — so enumeration of block k+1 overlaps the device
sweep of block k (double buffering, see ``base.py``).

Fleet-parallel batching: ``dispatch_blocks`` vmaps the same sweep over a
stacked :class:`repro.core.placement_backends.base.InstanceBatch` — one
XLA program places B instances' blocks, amortising the per-dispatch
overhead that dominates a Python loop of solo calls.  Ragged instances
arrive padded; the vmapped kernel threads each instance's traced
``n_t_eff``/``n_f_eff`` so padded columns are never read and verdicts
stay bit-identical to the numpy loop-over-instances reference.  Both the
instance axis (to a power of two) and the row axis are padded outside
jit, bounding recompiles to O(log B · log R) per (n_t, n_f) topology.
With ``shard=`` the instance axis is additionally laid out across a 1-D
device mesh via ``shard_map`` (clamped to the largest power of two that
the host's device count and the padded batch allow — a single-device
host degrades to the plain vmap, never an error).
"""

from __future__ import annotations

import functools

import numpy as np

from ... import trace
from .base import (
    BatchPlacement,
    InstanceBatch,
    PlacementOptions,
    fetch,
    prepare_block,
    register_backend,
    survivor_batch_tables,
    survivor_tables,
)
from .jax_runtime import configure_compile_cache, x64

__all__ = ["JaxPlacementBackend", "resolve_shard"]

_MIN_PAD = 8


def _pad_pow2(n: int, minimum: int = 1) -> int:
    """Next power of two >= n (>= minimum)."""
    p = minimum
    while p < n:
        p <<= 1
    return p


def _pad_rows(B: int) -> int:
    """Next power of two >= B (>= _MIN_PAD) — the static block height."""
    return _pad_pow2(B, _MIN_PAD)


@functools.cache
def _jitted_sweep():
    """Build the jit'd sweep lazily so importing this module stays cheap."""
    import jax

    from repro.kernels.ref import placement_sweep_ref

    return jax.jit(placement_sweep_ref, static_argnames=("repay_init",))


@functools.cache
def _jitted_resilient_sweep():
    """Jit'd resilience-mode sweep: primary AND worst-case-survivor pass.

    Both sweeps live in one jit program, so the second, constrained pass
    of ``opts.resilience`` costs one extra while_loop inside the same
    dispatch — not a second host round-trip.
    """
    import jax

    from repro.kernels.ref import placement_sweep_resilient_ref

    return jax.jit(placement_sweep_resilient_ref, static_argnames=("repay_init",))


def resolve_shard(shard: int | str | None, Bp: int) -> int:
    """Clamp a ``shard=`` request to a usable instance-axis mesh size.

    Returns the number of devices to lay the (padded, power-of-two)
    instance axis over: the largest power of two that is <= the request
    (``"auto"`` = all local jax devices), <= the host's device count, and
    <= ``Bp`` so the axis divides evenly.  ``None``, one device, or an
    empty batch all resolve to 1 — plain vmap, no mesh — which is the
    graceful single-device degrade the benchmarks rely on.
    """
    if shard is None or Bp == 0:
        return 1
    import jax

    n_dev = len(jax.devices())
    want = n_dev if shard == "auto" else int(shard)
    if want < 1:
        raise ValueError(f"shard must be >= 1 or 'auto', got {shard!r}")
    limit = min(want, n_dev, Bp)
    nd = 1
    while nd * 2 <= limit:
        nd *= 2
    return nd


@functools.cache
def _jitted_batch_sweep(n_shards: int):
    """Jit'd fleet-parallel sweep, optionally shard_map'd over devices.

    Cached per mesh size: ``n_shards == 1`` is the plain vmapped sweep;
    larger meshes wrap it in ``shard_map`` with the instance axis
    partitioned (every other operand axis replicated), so each device
    sweeps ``Bp / n_shards`` instances of the same compiled program.
    """
    import jax

    from repro.kernels.ref import placement_sweep_batch_ref

    if n_shards <= 1:
        return jax.jit(placement_sweep_batch_ref, static_argnames=("repay_init",))

    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("i",))

    def sweep(shares, iis, t_slr, t_cfg, n_t_eff, n_f_eff, resume_cost, *, repay_init):
        return jax.shard_map(
            functools.partial(placement_sweep_batch_ref, repay_init=repay_init),
            mesh=mesh,
            in_specs=(P("i"), P("i"), P("i"), P("i"), P("i"), P("i"), P()),
            out_specs=(P("i"), P("i"), P("i"), P("i")),
            # Every output is instance-axis partitioned, so the varying
            # manual axes check adds nothing.
            check_vma=False,
        )(shares, iis, t_slr, t_cfg, n_t_eff, n_f_eff, resume_cost)

    return jax.jit(sweep, static_argnames=("repay_init",))


@functools.cache
def _jitted_batch_resilient_sweep(n_shards: int):
    """Jit'd fleet-parallel resilience sweep, optionally shard_map'd.

    The resilience-mode twin of :func:`_jitted_batch_sweep`: three extra
    instance-axis operands carry the per-instance worst-case-survivor
    tables (``base.survivor_batch_tables``), partitioned alongside the
    primary tables on meshes > 1.
    """
    import jax

    from repro.kernels.ref import placement_sweep_batch_resilient_ref

    if n_shards <= 1:
        return jax.jit(
            placement_sweep_batch_resilient_ref, static_argnames=("repay_init",)
        )

    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("i",))

    def sweep(
        shares,
        iis,
        t_slr,
        t_cfg,
        n_t_eff,
        n_f_eff,
        t_slr_s,
        t_cfg_s,
        n_f_eff_s,
        resume_cost,
        *,
        repay_init,
    ):
        return jax.shard_map(
            functools.partial(
                placement_sweep_batch_resilient_ref, repay_init=repay_init
            ),
            mesh=mesh,
            in_specs=(P("i"),) * 9 + (P(),),
            out_specs=(P("i"), P("i"), P("i"), P("i")),
            check_vma=False,
        )(
            shares,
            iis,
            t_slr,
            t_cfg,
            n_t_eff,
            n_f_eff,
            t_slr_s,
            t_cfg_s,
            n_f_eff_s,
            resume_cost,
        )

    return jax.jit(sweep, static_argnames=("repay_init",))


@register_backend("jax")
class JaxPlacementBackend:
    """``lax.while_loop`` sweep, float64 via the scoped ``x64``."""

    name = "jax"
    async_dispatch = True

    def __init__(self) -> None:
        configure_compile_cache()

    @classmethod
    def available(cls) -> bool:
        try:
            import jax  # noqa: F401
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
        """Enqueue the jit'd sweep; the returned resolver syncs verdicts.

        The outputs stay on-device until the resolver runs, so callers
        can overlap enumeration/dispatch of the next block with this
        one's execution (see the ``dispatch_block`` contract in
        ``base.py``).
        """
        with trace.span("sched.prepare", "prepare_us"):
            shares, iis, t_slr_arr, t_cfg_arr, opts, early = prepare_block(
                shares, iis, t_slr, t_cfg, opts
            )
            if early is not None:
                return lambda: early
            B = shares.shape[0]
            Bp = _pad_rows(B)
            if Bp != B:
                shares = np.pad(shares, ((0, Bp - B), (0, 0)))
            tables = (iis, t_slr_arr, t_cfg_arr)
            if opts.resilience:
                tables += survivor_tables(t_slr_arr, t_cfg_arr, opts.resilience)
        trace.note(padded_rows=Bp)
        with x64(), trace.launch((shares, *tables)):
            sweep = _jitted_resilient_sweep() if opts.resilience else _jitted_sweep()
            outs = sweep(
                shares, *tables, np.float64(opts.resume_cost), repay_init=opts.repay_init
            )

        def resolve() -> BatchPlacement:
            out = [a[:B] for a in fetch(outs)]
            return BatchPlacement(
                feasible=out[0].astype(bool),
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
        shard: int | str | None = None,
    ):
        """Enqueue one vmapped sweep; resolver returns untrimmed arrays.

        The zero-copy variant of :meth:`dispatch_blocks` (see the raw
        batching contract in ``base.py``): the resolver yields the four
        verdict arrays ``(feasible, placed_tasks, n_splits,
        devices_used)`` with shape ``(B', Rp)`` where ``B' >= len(batch)``
        and ``Rp >= max(n_rows)`` — entries beyond an instance's
        ``n_rows[i]`` (or beyond ``len(batch)``) are padding and
        undefined; live entries are bit-identical to the solo sweep.
        Returns ``None`` for degenerate batches the traced sweep cannot
        express (zero instances / zero-width task or device tables) —
        callers fall back to the trimmed per-instance surface.
        """
        B = len(batch)
        if B == 0:
            return None
        if opts is None:
            opts = PlacementOptions()
        if batch.shares.shape[2] == 0 or batch.t_slr.shape[1] == 0:
            # Degenerate padded widths (no tasks / no devices anywhere in
            # the batch): the traced sweep cannot index zero-width tables,
            # but prepare_block's early paths answer every instance.
            return None
        with trace.span("sched.prepare", "prepare_us"):
            Bp = _pad_pow2(B)
            Rp = _pad_rows(batch.shares.shape[1])
            shares = batch.shares
            pad_b, pad_r = Bp - B, Rp - shares.shape[1]
            if opts.resilience:
                # Survivor tables are computed per live instance before padding
                # (padded instances keep n_f_eff_s == 0, matching their
                # n_t_eff == 0 no-op status).
                t_slr_s, t_cfg_s, n_f_eff_s = survivor_batch_tables(
                    batch.t_slr, batch.t_cfg, batch.n_f_eff, opts.resilience
                )
            if pad_b or pad_r:
                # Padded instances carry n_t_eff == 0 (all-feasible no-ops);
                # padded rows are garbage-swept and trimmed by the resolver.
                shares = np.pad(shares, ((0, pad_b), (0, pad_r), (0, 0)))
            iis = np.pad(batch.iis, ((0, pad_b), (0, 0))) if pad_b else batch.iis
            t_slr = np.pad(batch.t_slr, ((0, pad_b), (0, 0))) if pad_b else batch.t_slr
            t_cfg = np.pad(batch.t_cfg, ((0, pad_b), (0, 0))) if pad_b else batch.t_cfg
            n_t_eff = np.pad(batch.n_t_eff, (0, pad_b)) if pad_b else batch.n_t_eff
            n_f_eff = np.pad(batch.n_f_eff, (0, pad_b)) if pad_b else batch.n_f_eff

            n_shards = resolve_shard(shard, Bp)
            args = (shares, iis, t_slr, t_cfg, n_t_eff, n_f_eff)
            if opts.resilience:
                if pad_b:
                    t_slr_s = np.pad(t_slr_s, ((0, pad_b), (0, 0)))
                    t_cfg_s = np.pad(t_cfg_s, ((0, pad_b), (0, 0)))
                    n_f_eff_s = np.pad(n_f_eff_s, (0, pad_b))
                args += (t_slr_s, t_cfg_s, n_f_eff_s)
        trace.note(padded_rows=Rp)
        with x64(), trace.launch(args):
            sweep = (
                _jitted_batch_resilient_sweep(n_shards)
                if opts.resilience
                else _jitted_batch_sweep(n_shards)
            )
            outs = sweep(*args, np.float64(opts.resume_cost), repay_init=opts.repay_init)

        return lambda: tuple(fetch(outs))

    def dispatch_blocks(
        self,
        batch: InstanceBatch,
        opts: PlacementOptions | None = None,
        *,
        shard: int | str | None = None,
    ):
        """Enqueue one vmapped sweep over all B instances' blocks.

        See the fleet-parallel batching contract in ``base.py``: the
        resolver returns one :class:`BatchPlacement` per instance,
        trimmed to its live rows, bit-identical to the numpy
        loop-over-instances reference.  ``shard`` lays the instance axis
        across a device mesh (clamped via :func:`resolve_shard`; a
        single-device host silently runs the plain vmap).
        """
        B = len(batch)
        if B == 0:
            return lambda: []
        raw = self.dispatch_blocks_raw(batch, opts, shard=shard)
        if raw is None:
            from .base import place_instance_blocks

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
        shard: int | str | None = None,
    ) -> list[BatchPlacement]:
        return self.dispatch_blocks(batch, opts, shard=shard)()
