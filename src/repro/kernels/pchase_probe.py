"""Pointer-chase probe — Pallas TPU kernel (paper §IV-A, TPU-native).

The GPU p-chase reads a per-load cycle counter; TPU Pallas has no in-kernel
clock (DESIGN.md adaptation note 1), so the kernel executes a dependent-load
chain of known length and the *caller* times the call: the per-load cost is
the slope of wall time over chain length, which cancels dispatch.

The chase buffer stays in HBM (``memory_space=pl.ANY``).  Each load of the
chain is one DMA of the 128-lane row holding the cursor into an SMEM
scratch, and the next cursor is read from SMEM — so every step is a real
HBM round trip that cannot start before the previous one ends.  (A 128-lane
int32 row, 512 B, is the smallest slice Mosaic lets a DMA take from a tiled
HBM array.)  The buffer is a random single cycle (Sattolo) so nothing can
run ahead.  The kernel writes ``[final_cursor, checksum]`` to SMEM: the
chain cannot be dead-code-eliminated, and both values are the correctness
contract checked against ``pchase_reference``.

``pchase_kernel_batch`` maps a whole §IV-B size sweep onto the grid: row i
walks its own HBM-resident cycle (padded to a shared width) for its own
chain length.  Chain lengths are scalar-prefetch *data*, so sweeps with
different step counts reuse one compiled kernel.  ``pchase_kernel`` is the
one-row case.

``eviction_kernel_batch`` extends the same trick to the eviction-pattern
probes (paper §IV-F/§IV-G/§IV-H, Fig. 3): each grid row first walks an
*evictor* chain (warm phase over buffer B) and then a *probe* chain
(buffer A), with both phase lengths carried as per-row data.  A row with
``warm_steps == 0`` is a plain p-chase row, the bit-identity anchor the
tests pin.

``interpret`` is ``False`` (compile for the TPU) unless the caller asks
for the interpreter by name: ``True`` runs Pallas' fast interpreter and
``pltpu.InterpretParams()`` the TPU-semantics one, which checks the DMAs
and semaphores as Mosaic would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pchase_kernel", "pchase_kernel_batch", "pchase_reference",
           "eviction_kernel_batch", "eviction_reference", "LANES"]

LANES = 128          # int32 elements per DMA'd row


def _walk(buf_hbm, row, steps, checksum):
    """``steps`` dependent loads over ``buf_hbm[row]`` from slot 0, one row
    DMA each -> (final cursor, checksum).

    The SMEM landing row and its semaphore are scoped to the walk: as grid
    scratch, the interpreter would carry them across grid rows and run the
    loop several times slower."""
    def walk(idx, sem):
        def body(_, carry):
            cursor, checksum = carry
            copy = pltpu.make_async_copy(buf_hbm.at[row, cursor // LANES],
                                         idx, sem)
            copy.start()
            copy.wait()
            nxt = idx[0, cursor % LANES]
            return nxt, checksum + nxt

        return jax.lax.fori_loop(0, steps, body, (jnp.int32(0), checksum))

    return pl.run_scoped(walk, pltpu.SMEM((1, LANES), jnp.int32),
                         pltpu.SemaphoreType.DMA(()))


def _batch_kernel(steps_ref, perm_hbm, out_ref):
    r = pl.program_id(0)
    cursor, checksum = _walk(perm_hbm, r, steps_ref[r], jnp.int32(0))
    out_ref[2 * r] = cursor
    out_ref[2 * r + 1] = checksum


def _evict_kernel(warm_ref, probe_ref, evictor_hbm, perm_hbm, out_ref):
    r = pl.program_id(0)
    _, warm_sum = _walk(evictor_hbm, r, warm_ref[r], jnp.int32(0))
    cursor, checksum = _walk(perm_hbm, r, probe_ref[r], warm_sum)
    out_ref[2 * r] = cursor
    out_ref[2 * r + 1] = checksum


def _rows(buf: jax.Array) -> jax.Array:
    """(R, N) -> (R, ceil(N/128), 1, 128): one DMA-able row per lane group.

    Zero padding is never read: every chain starts at 0 and stays on its
    cycle."""
    r, n = buf.shape
    pad = -n % LANES
    if pad:
        buf = jnp.pad(buf, ((0, 0), (0, pad)))
    return buf.reshape(r, (n + pad) // LANES, 1, LANES)


def _chase_call(kernel, n_prefetch: int, n_bufs: int, rows: int, interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch, grid=(rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_bufs,
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=jax.ShapeDtypeStruct((2 * rows,), jnp.int32),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def pchase_kernel_batch(perms: jax.Array, steps: jax.Array, *,
                        interpret=False) -> jax.Array:
    """Grid-batched p-chase: one kernel launch for a whole size sweep.

    ``perms`` (R, N) int32 — row i is a single-cycle permutation over the
    slots it uses, zero-padded to the shared width (the chain starts at 0
    and never leaves its cycle, so padding is never read).  It stays in
    HBM; each load is a DMA.

    **Chain-lengths-as-data contract**: ``steps`` (R,) int32 carries each
    row's dependent-chain length as kernel *data* (scalar prefetch) —
    never baked in as a static/compile-time argument.  This is what lets
    one compiled kernel serve every row of a sweep (and every sweep with
    the same (R, N) shape): rows with different chain lengths differ only
    in the value read from ``steps``, so no row forces a recompile.
    Consequence for callers: changing a row's chain length must never
    change the kernel's shape signature — resize ``perms`` padding, not
    the grid.

    Returns (R, 2) int32 ``[final_cursor, checksum]`` rows, the correctness
    contract of ``pchase_reference``.
    """
    r = perms.shape[0]
    out = _chase_call(_batch_kernel, 1, 1, r, interpret)(
        steps.astype(jnp.int32), _rows(perms))
    return out.reshape(r, 2)


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def pchase_kernel(perm: jax.Array, *, iters: int,
                  interpret=False) -> jax.Array:
    """perm (N,) int32 single-cycle permutation -> [final_cursor, checksum]."""
    steps = jnp.full((1,), iters, jnp.int32)
    return pchase_kernel_batch(perm[None], steps, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def eviction_kernel_batch(perms: jax.Array, evictors: jax.Array,
                          warm_steps: jax.Array, probe_steps: jax.Array, *,
                          interpret=False) -> jax.Array:
    """Grid-batched eviction-pattern probe (Fig. 3 warm-B / probe-A).

    Row i walks its *evictor* cycle ``evictors[i]`` for ``warm_steps[i]``
    dependent loads (warming the conflicting working set), then walks its
    *probe* cycle ``perms[i]`` for ``probe_steps[i]`` loads — the phase the
    caller times to see whether the warm phase evicted the probe array.
    Both phase lengths follow the chain-lengths-as-data contract of
    ``pchase_kernel_batch``: they are per-row kernel *data*, so one compiled
    kernel serves heterogeneous amount/sharing/cu-sharing rows of any mix,
    and changing a row's phase lengths never forces a recompile.

    ``perms`` (R, N) and ``evictors`` (R, M) are zero-padded single-cycle
    permutations kept in HBM; both chains start at slot 0 and never leave
    their cycle.  Returns (R, 2) int32 ``[final_probe_cursor, checksum]``
    where the checksum covers both phases.  A row with ``warm_steps == 0``
    is bit-identical to the same ``pchase_kernel_batch`` row.
    """
    r = perms.shape[0]
    out = _chase_call(_evict_kernel, 2, 2, r, interpret)(
        warm_steps.astype(jnp.int32), probe_steps.astype(jnp.int32),
        _rows(evictors), _rows(perms))
    return out.reshape(r, 2)


def _walk_reference(perm, steps: int, checksum: int) -> tuple[int, int]:
    """``steps`` loads of ``perm`` from slot 0, adding each to ``checksum``
    with int32 wrap-around (the kernel's arithmetic)."""
    import numpy as np

    p = np.asarray(perm)
    cursor = 0
    for _ in range(int(steps)):
        cursor = int(p[cursor])
        checksum = (checksum + cursor + 2**31) % 2**32 - 2**31
    return cursor, checksum


def eviction_reference(perm, evictor, warm_steps: int,
                       probe_steps: int) -> tuple[int, int]:
    """Pure-Python two-phase walk: the contract for ``eviction_kernel_batch``."""
    _, warm_sum = _walk_reference(evictor, warm_steps, 0)
    return _walk_reference(perm, probe_steps, warm_sum)


def pchase_reference(perm, steps: int) -> tuple[int, int]:
    """Pure-Python chain walk: the correctness contract for both kernels."""
    return _walk_reference(perm, steps, 0)
