"""Per-layer metric ``observer_ms_per_step.fused``: see program_spans.observer_ms_per_step."""
from program_spans import observer_ms_per_step as read  # noqa: F401
