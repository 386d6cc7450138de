"""Per-layer metric ``loop_host_ms_per_step.fused``: see program_spans.loop_host_ms_per_step."""
from program_spans import loop_host_ms_per_step as read  # noqa: F401
