"""Pallas TPU kernel: streaming scaled-sum over HBM-resident blocks.

The hot op of the warm-read path (bench config #1 and any
``device-side scan`` consumer): read every cached byte once, multiply
by a scalar, reduce. XLA's fused reduce already runs near HBM peak;
this kernel exists to (a) own the schedule explicitly — a gridded
``BlockSpec`` pipeline double-buffers the HBM->VMEM DMAs against the
VPU reduce with no fusion-heuristic dependence — and (b) serve as the
repo's reference pallas pattern (guide: ``pallas_guide.md`` grid/
BlockSpec pipelining).

Mosaic compiles it on a TPU; anywhere else pass ``interpret=True``.
"""

from __future__ import annotations

_LANES = 1024  # 8x128 VPU tile multiples
_ROWS = 512    # rows per grid step: 512x1024 int32 = 2 MiB VMEM/block
# Candidate block heights for calibration: at 819 GB/s a 2 MiB block is
# only ~2.6 us of DMA, so fixed per-grid-step cost can be a few percent;
# taller blocks amortize it. bench.py times each and keeps the winner.
# The double-buffered block must fit Mosaic's scoped-VMEM budget (16 MiB
# on a v5e): 2048 rows = 2 x 8 MiB is the tallest that does. 4096 rows
# compiles in a straight-line jit but is refused inside a loop body
# ("scoped allocation 32M, limit 16M"), which is how bench.py runs it —
# chip_smoke.py checks every height listed here in that form.
CALIBRATION_ROWS = (512, 1024, 2048)


def _kernel(x_ref, s_ref, o_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[0, 0] = jnp.int32(0)

    # VPU multiply-reduce over this block; accumulation is safe because
    # the TPU grid executes sequentially
    o_ref[0, 0] += jnp.sum(x_ref[:] * s_ref[0, 0])


def scaled_sum(x, scale, *, rows: int = _ROWS, interpret: bool = False):
    """``sum(x * scale)`` for int32 ``x`` of size divisible by
    ``rows * _LANES`` (use ``pad_to_kernel_shape`` otherwise — zeros
    are reduction-neutral). Trace-time shapes, so calling this inside
    the consumer's ``jit`` compiles it once."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if x.size % (rows * _LANES):
        raise ValueError(
            f"input size {x.size} is not a multiple of "
            f"{rows * _LANES}; pad with pad_to_kernel_shape() — "
            f"flooring would silently drop the tail from the reduction")
    flat = x.reshape(-1, _LANES)
    tiles = flat.shape[0] // rows
    grid_spec = pl.GridSpec(
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
    )
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(flat, scale.reshape(1, 1).astype(jnp.int32))
    return out[0, 0]


def pad_to_kernel_shape(arr, *, rows: int = _ROWS):
    """Zero-pad a flat int32 array up to the kernel's block multiple."""
    import jax.numpy as jnp

    block = rows * _LANES
    n = arr.size
    rem = (-n) % block
    if rem:
        arr = jnp.concatenate(
            [arr.reshape(-1), jnp.zeros((rem,), dtype=arr.dtype)])
    return arr.reshape(-1, _LANES)
