"""Pallas TPU kernels for the serving hot spots, with jnp oracles.

* ``flash_attention``    — prefill causal attention (GQA via index-map
  folding)
* ``paged_attention``    — decode over block-table KV pages (vLLM→TPU
  port)
* ``ssd_scan``           — Mamba-2 chunked state-space scan
* ``decode_advance_jnp`` — the jax DES backend's fused decode-advance
  round (plain jnp, float64 event times)

The Pallas kernels are validated with ``interpret=True`` on CPU against
:mod:`repro.kernels.ref` (numeric tolerance) and compiled by Mosaic on
TPU. Off-TPU they default to interpreter mode so CPU CI still executes
the kernel bodies. None of them is on the serving path today: ``models/``
uses the jnp attention in :mod:`repro.models.layers`.
"""

from repro.kernels.ops import flash_attention, paged_attention, ssd_scan
from repro.kernels.sim_decode import decode_advance_jnp
from repro.kernels import ref

__all__ = [
    "flash_attention",
    "paged_attention",
    "ssd_scan",
    "decode_advance_jnp",
    "ref",
]
