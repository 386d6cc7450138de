"""Per-layer metric ``kda_decay_span_max.ling``: see ling_readers.kda_decay_span_max."""
from ling_readers import kda_decay_span_max as read  # noqa: F401
