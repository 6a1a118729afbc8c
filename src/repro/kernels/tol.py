"""Third-octave level kernel: banded PSD integration + dB conversion.

TOL = 10*log10((psd @ M) * df) + gain, with M the fractional band-membership
matrix from repro.core.tol.  The matmul is tall-skinny (n_bins x ~33 bands);
M stays resident in VMEM across the whole grid and the log runs on the VPU,
so the per-record cost is one pass over the PSD row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import common

_PREC = jax.lax.Precision.HIGHEST


def _body(psd_ref, m_ref, o_ref, *, df: float, gain_db: float):
    power = jnp.dot(psd_ref[...], m_ref[...], precision=_PREC,
                    preferred_element_type=jnp.float32) * df
    o_ref[...] = 10.0 * jnp.log10(jnp.maximum(power, 1e-30)) + gain_db


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def tol_levels(psd: jnp.ndarray, band_matrix: jnp.ndarray, p,
               block_records: int = 128, interpret: bool | None = None
               ) -> jnp.ndarray:
    """(n_records, n_bins) x (n_bins, n_bands) -> (n_records, n_bands) dB."""
    if interpret is None:
        interpret = common.use_interpret()
    n_rec, n_bins = psd.shape
    n_bands = band_matrix.shape[1]

    rpad = common.round_up(n_rec, block_records)
    bpad = common.round_up(n_bins, 128)
    gpad = common.round_up(n_bands, 128)
    x = common.pad_axis(common.pad_axis(psd.astype(jnp.float32), 0, rpad),
                        1, bpad)
    # Padded bands integrate to zero power -> log floor; sliced off below.
    m = jnp.pad(band_matrix.astype(jnp.float32),
                ((0, bpad - n_bins), (0, gpad - n_bands)))

    grid = (rpad // block_records,)
    out = pl.pallas_call(
        functools.partial(_body, df=float(p.df), gain_db=float(p.gain_db)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_records, bpad), lambda i: (i, 0)),
            pl.BlockSpec((bpad, gpad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_records, gpad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rpad, gpad), jnp.float32),
        interpret=interpret,
        name="tol_levels",
    )(x, jnp.asarray(m))
    return out[:n_rec, :n_bands]
