"""Per-layer metric ``dispatch_us_per_arg.ouro``: see lm_spans.dispatch_us_per_arg."""
from lm_spans import dispatch_us_per_arg as read  # noqa: F401
