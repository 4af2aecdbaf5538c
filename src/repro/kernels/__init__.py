"""Pallas TPU kernels for the DWN hot spots the paper optimizes:
thermometer encoding, LUT-layer evaluation, popcount/argmax — plus the
fused whole-accelerator kernel (beyond-paper; bits never leave VMEM).
Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with interpret/TPU switch + padding), ref.py (pure-jnp oracle).

Every stage also has a *packed* variant operating on uint32 bitplanes
(32 logical bits per word — see ``repro.core.bitpack`` for the format):
``encode_packed`` emits packed words straight from the compare,
``evaluate_packed`` forms LUT addresses with shift/AND on the words,
``classify_packed`` popcounts masked words (SWAR), and
``fused.ops.forward_packed`` runs the whole model in one pallas_call
(the served kernel).  ``autotune`` picks its block shapes per (model,
batch bucket, device) and persists winners (docs/autotune.md)."""
from . import thermometer, lut_eval, popcount, fused, flash_attn, autotune
