"""Per-layer metric ``programs_per_step.ouro``: see readers.programs_per_step."""
from readers import programs_per_step as read  # noqa: F401
