"""Per-layer metric ``hbm_peak_gib.fused``: see readers.hbm_peak_gib."""
from readers import hbm_peak_gib as read  # noqa: F401
