"""Pallas TPU kernels for the substrate's compute hot spots.

The paper's contribution is control-plane (process management), so these
kernels serve the model substrate: flash attention (GQA/window/softcap),
the Mamba2 SSD chunked scan, and the chunked mLSTM recurrence.  Each
kernel module ships ``<name>.py`` (pl.pallas_call + BlockSpec tiling),
an ``ops.py`` jit'd wrapper, and a ``ref.py`` pure-jnp oracle.  The
wrappers compile for the TPU; tests validate them against the oracles
with ``interpret=True`` on the CPU and compile them for a described v5e
chip (``tests/test_tpu_compile.py``).
"""
from .ops import flash_attention, mlstm_scan, ssd_scan

__all__ = ["flash_attention", "mlstm_scan", "ssd_scan"]
