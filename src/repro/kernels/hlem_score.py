"""Pallas TPU kernel for HLEM-VMP host scoring (paper Eqs. 3-11).

TPU adaptation of the hot loop: at Google-trace scale the simulator re-scores
~12.6 k hosts for every one of ~28.8 M allocations; the Java original walks
host objects one by one.  Here the host axis is laid out along TPU *lanes*
(128-wide) with the D=4 resource dims on sublanes, and the whole scoring —
four data-dependent reduction stages — runs as ONE ``pallas_call`` using the
TPU's sequential-grid guarantee to carry scratch accumulators across stages:

  stage 0: global per-dim min/max of free capacity     (Eq. 3 prerequisites)
  stage 1: column sums of standardized capacity        (Eq. 4 denominator)
  stage 2: Σ p·ln p entropy partials                   (Eq. 5)
  stage 3: weights w_d (Eqs. 6-8) + scores HS/AHS      (Eqs. 9-11), written out

Grid = (B VMs, 4 stages, n_host_blocks); scratch persists across each VM's
stages, so no HBM round-trips between stages beyond the single streaming of
host data per stage (4 × n × D × 4 B traffic per VM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-12
_BIG = 3.4e38
SUB = 8          # sublane padding for the D=4 resource dims
DEFAULT_BLOCK = 512


def _kernel(alpha_ref, free_ref, spot_ref, mask_ref, out_ref,
            lo_ref, hi_ref, col_ref, plp_ref, m_ref):
    # grid (B, 4, nblk): the same 4-stage pipeline per batch element; scratch
    # accumulators are re-initialized at (stage 0, block 0) of every element
    # thanks to the TPU's sequential-grid guarantee.
    elem = pl.program_id(0)
    stage = pl.program_id(1)
    jblk = pl.program_id(2)

    free = free_ref[...]          # (SUB, BN) — rows 0..3 are resource dims
    spot = spot_ref[...]          # (SUB, BN)
    mask = mask_ref[...]          # (1, BN) float32 {0,1}
    maskb = mask > 0.5

    @pl.when(jnp.logical_and(stage == 0, jblk == 0))
    def _init():
        lo_ref[...] = jnp.full_like(lo_ref, _BIG)
        hi_ref[...] = jnp.full_like(hi_ref, -_BIG)
        col_ref[...] = jnp.zeros_like(col_ref)
        plp_ref[...] = jnp.zeros_like(plp_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    @pl.when(stage == 0)
    def _minmax():
        fmin = jnp.where(maskb, free, _BIG).min(axis=1, keepdims=True)
        fmax = jnp.where(maskb, free, -_BIG).max(axis=1, keepdims=True)
        lo_ref[...] = jnp.minimum(lo_ref[...], fmin)
        hi_ref[...] = jnp.maximum(hi_ref[...], fmax)
        m_ref[...] = m_ref[...] + jnp.sum(mask, axis=1, keepdims=True)

    def _standardize():
        lo = lo_ref[...]
        hi = hi_ref[...]
        span = hi - lo
        degen = span <= _EPS
        c = jnp.where(degen, 1.0, (free - lo) / jnp.where(degen, 1.0, span))
        return c * mask  # broadcast (1,BN) over sublanes

    @pl.when(stage == 1)
    def _colsum():
        c = _standardize()
        col_ref[...] = col_ref[...] + jnp.sum(c, axis=1, keepdims=True)

    def _proportions():
        c = _standardize()
        col = col_ref[...]
        m = m_ref[0, 0]
        p = jnp.where(col > _EPS, c / jnp.where(col > _EPS, col, 1.0),
                      mask / jnp.maximum(m, 1.0))
        return p * mask

    @pl.when(stage == 2)
    def _entropy():
        p = _proportions()
        plogp = jnp.where(p > _EPS, p * jnp.log(jnp.maximum(p, _EPS)), 0.0)
        plp_ref[...] = plp_ref[...] + jnp.sum(plogp, axis=1, keepdims=True)

    @pl.when(stage == 3)
    def _score():
        m = m_ref[0, 0]
        k = jnp.where(m > 1.0, 1.0 / jnp.log(jnp.maximum(m, 2.0)), 0.0)
        e = -k * plp_ref[...]                     # (SUB, 1)
        d_real = 4
        # only rows 0..3 are real dims; padded rows carry col==0 & plp==0 ->
        # e==0, g==1 — mask them out of the weight normalization.  Mosaic
        # lowers integer iotas only.
        real = jax.lax.broadcasted_iota(jnp.int32, e.shape, 0) < d_real
        g = jnp.where(real, 1.0 - e, 0.0)
        gsum = jnp.sum(g)
        w = jnp.where(gsum > _EPS, g / jnp.where(gsum > _EPS, gsum, 1.0),
                      jnp.where(real, 1.0 / d_real, 0.0))  # (SUB, 1)
        c = _standardize()
        hs = jnp.sum(c * w, axis=0, keepdims=True)          # (1, BN)
        sl = jnp.sum(spot * w, axis=0, keepdims=True)
        hs = hs * (1.0 + alpha_ref[elem] * sl)
        out_ref[...] = jnp.where(maskb, hs, -_BIG)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def hlem_score_pallas(free: jax.Array, mask: jax.Array, spot_frac: jax.Array,
                      alpha: jax.Array, *, block: int = DEFAULT_BLOCK,
                      interpret: bool = False) -> jax.Array:
    """Drop-in replacement for ``repro.core.hlem.hlem_scores_jax``.

    free (n, D) float, mask (n,) bool, spot_frac (n, D), alpha scalar.
    Returns (n,) float32 scores with -3.4e38 at masked hosts.  The B=1 case
    of :func:`hlem_score_pallas_batch`.
    """
    alphas = jnp.reshape(jnp.asarray(alpha, jnp.float32), (1,))
    return hlem_score_pallas_batch(free, mask[None], spot_frac, alphas,
                                   block=block, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def hlem_score_pallas_batch(
    free: jax.Array, masks: jax.Array, spot_frac: jax.Array,
    alphas: jax.Array, *, block: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Batched scoring: B VM candidate sets × n hosts in ONE ``pallas_call``.

    Drop-in accelerator path for ``repro.core.hlem.hlem_scores_batch_np``:
    free (n, D) shared host state, masks (B, n) bool per-VM feasibility,
    spot_frac (n, D), alphas (B,) per-VM adjustment.  Returns (B, n) float32
    scores with -3.4e38 at masked hosts.

    Grid = (B, 4 stages, n_host_blocks): the batch axis is the leading grid
    dimension over the 4-stage reduction pipeline; host data is streamed
    once per (element, stage).  Masks and outputs are laid out (B, 1, n_pad)
    so each element's (1, block) tile spans the array's own sublane dim (the
    TPU tiling rule: the last two block dims divide (8, 128) or equal the
    array's); the B alphas sit whole in SMEM, indexed by the element id.
    """
    n, d = free.shape
    b = masks.shape[0]
    assert d <= SUB, f"at most {SUB} resource dims supported, got {d}"
    n_pad = max(pl.cdiv(n, block), 1) * block

    def to_tiles(x):  # (n, D) -> (SUB, n_pad), host axis on lanes
        x = jnp.asarray(x, jnp.float32)
        x = jnp.pad(x, ((0, n_pad - n), (0, SUB - d)))
        return x.T

    free_t = to_tiles(free)
    spot_t = to_tiles(spot_frac)
    masks_t = jnp.pad(masks.astype(jnp.float32),
                      ((0, 0), (0, n_pad - n)))[:, None, :]
    alphas_arr = jnp.asarray(alphas, jnp.float32).reshape(b)

    nblk = n_pad // block
    out = pl.pallas_call(
        _kernel,
        grid=(b, 4, nblk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((SUB, block), lambda bb, s, j: (0, j)),
            pl.BlockSpec((SUB, block), lambda bb, s, j: (0, j)),
            pl.BlockSpec((None, 1, block), lambda bb, s, j: (bb, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, block), lambda bb, s, j: (bb, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, n_pad), jnp.float32),
        scratch_shapes=[
            # lo, hi, col, plogp accumulators (SUB,1) + candidate count (1,1)
            pltpu.VMEM((SUB, 1), jnp.float32),
            pltpu.VMEM((SUB, 1), jnp.float32),
            pltpu.VMEM((SUB, 1), jnp.float32),
            pltpu.VMEM((SUB, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(alphas_arr, free_t, spot_t, masks_t)
    return out[:, 0, :n]
