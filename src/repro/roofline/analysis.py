"""TPU v5e per-chip hardware constants.

The training-step DAGs (``appdag.plans``, ``core/comm_schedule.py``) size
their compute nodes and transfers from these: a unit's backward is its
FLOPs at ``PEAK_FLOPS``, an optimizer update its bytes at ``HBM_BW``, and
a gradient bucket its bytes at ``LINK_BW``.
"""

from __future__ import annotations

PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s/link (ICI)
