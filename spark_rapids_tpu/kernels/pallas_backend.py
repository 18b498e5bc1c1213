"""Pallas (Mosaic) kernels — the ``pallas`` rung of the backend ladder.

[REF: the reference's hot operators are hand-written CUDA in libcudf;
 PAPER.md's blueprint maps that slot to Pallas on TPU.]

What is Pallas today vs. the fused-XLA rung: the hash-grouped layout's
mixing loop runs as a hand-scheduled VPU kernel with the limb block
resident in VMEM (``hash_pairs``), while the stable sort and the
segmented scans around it stay XLA-HLO — Mosaic has no vectorized
VMEM gather on current chips, so a full open-addressing build+probe
kernel is the roadmap item, not this PR.  The kernel is pure uint32
arithmetic (element-wise shifts/mults/xors — exactly the VPU's lane
ops) and transcribes ``hash_layout.mix_rounds`` line for line, so the
``pallas`` and ``fused`` rungs are bit-identical by construction; the
interpret-mode test in tests/test_kernels.py pins that on CPU.

Never imported on the hot path off-TPU: the dispatcher resolves
``pallas → fused`` when ``jax.default_backend() != "tpu"``.  On a TPU
``auto`` takes this rung, and a lowering failure there RAISES out of
the query: ``cached_kernel`` calls the jitted kernel directly unless a
fault is armed or a breaker is already open, so nothing degrades in
silence.  tests/test_chip_compile.py compiles the kernel for a
described v5e under x64 so that such a failure is met before the chip.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# rows per grid step: one VREG-friendly lane block, small enough that
# (limbs × block) stays far under VMEM even for wide key sets
BLOCK_ROWS = 4096


def available() -> bool:
    """Pallas rung usable here? (TPU only — CPU/GPU degrade to fused.)"""
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def hash_pairs(his: jnp.ndarray, los: jnp.ndarray,
               interpret: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mix L 64-bit words (as [L, n] u32 lane pairs) per row → (hi, lo).

    Grid over row blocks; each step loads its [L, BLOCK] slab into VMEM
    and runs the static-L mixing loop entirely on the VPU.  Bit-equal
    to the jnp loop in ``hash_layout.hash_limbs`` (same u32 ops in the
    same order).  ``interpret=True`` runs the kernel on the host for
    the CPU bit-identity test.
    """
    from jax.experimental import pallas as pl
    limbs, n = his.shape
    blk = min(BLOCK_ROWS, n)
    if n % blk:
        # capacities are pow2 (or sums of pow2s ≥ 16) so this only
        # trips on tiny probe shapes — shrink to the exact size
        blk = n

    def kernel(hi_ref, lo_ref, oh_ref, ol_ref):
        from spark_rapids_tpu.kernels.hash_layout import mix_rounds
        h = jnp.zeros((blk,), jnp.uint32)
        l = jnp.zeros((blk,), jnp.uint32)
        for j in range(limbs):  # static: unrolled into straight VPU ops
            h, l = mix_rounds(h, l, hi_ref[j, :], lo_ref[j, :])
        oh_ref[:] = h
        ol_ref[:] = l

    # index maps must yield int32: the engine runs under x64, where a
    # Python literal traces as i64 and Mosaic refuses the mixed
    # (i64, i32) return
    zero = np.int32(0)
    oh, ol = pl.pallas_call(
        kernel,
        grid=(n // blk,),
        in_specs=[pl.BlockSpec((limbs, blk), lambda i: (zero, i)),
                  pl.BlockSpec((limbs, blk), lambda i: (zero, i))],
        out_specs=[pl.BlockSpec((blk,), lambda i: (i,)),
                   pl.BlockSpec((blk,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32),
                   jax.ShapeDtypeStruct((n,), jnp.uint32)],
        interpret=interpret,
    )(his, los)
    return oh, ol
